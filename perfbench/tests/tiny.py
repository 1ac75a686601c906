"""Tiny cell specs for the CPU tests: the program's smoke widths."""
import copy

TRAIN_CONFIG = {
    "name": "tiny-train", "arch": "dense", "program_arch": "codeqwen1.5-7b",
    "program_preset": "smoke", "hidden_size": 128,
    "intermediate_size": 256, "num_hidden_layers": 1,
    "num_attention_heads": 4, "num_key_value_heads": 4, "vocab_size": 512,
    "rope_theta": 1000000.0, "rms_norm_eps": 1e-05,
    "attention_bias": True, "tie_word_embeddings": False}

SERVE_CONFIG = dict(TRAIN_CONFIG, name="tiny-serve", num_hidden_layers=2,
                    program_arch="qwen2.5-32b", num_key_value_heads=1)

OPT = {"lr": 3e-4, "beta2": 0.95, "eps": 1e-8, "weight_decay": 0.1,
       "clip_norm": 1.0, "warmup_steps": 0, "total_steps": 100000}


def train_spec(limits):
    return {"name": "tiny-train", "chips": 1, "config": TRAIN_CONFIG,
            "traffic": {"kind": "train", "seq_len": 64, "batch": 2},
            "cell": {"quant": "nvfp4", "remat": True, "optimizer": OPT,
                     "limits": dict(limits)},
            "end_to_end": [], "per_layer": []}


def serve_spec(limits):
    return {"name": "tiny-serve", "chips": 1, "config": SERVE_CONFIG,
            "traffic": {"kind": "serve",
                        "prompt": {"median": 24, "sigma": 0.5, "min": 16,
                                   "max": 32},
                        "output": {"median": 24, "sigma": 0.5, "min": 8,
                                   "max": 48},
                        "load": 0.9, "backlog": 0.5, "arrival_seed": 5},
            "cell": {"engine": {"slots": 4, "max_len": 96,
                                "decode_chunk": 4, "page_size": 16,
                                "kv_format": "nvfp4"},
                     "check": {"tokens": 64, "most_requests": 4},
                     "limits": dict(limits)},
            "end_to_end": [], "per_layer": []}


def copy_spec(spec):
    return copy.deepcopy(spec)
