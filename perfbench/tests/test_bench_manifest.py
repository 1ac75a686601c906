"""BENCHMARK.json and the data files the harness finds by name."""
import json
import math
import os
import pathlib
import re
import subprocess
import sys

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
ROOT = HERE.parent
sys.path.insert(0, str(HERE))

import traffic as tr  # noqa: E402

MAN = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = {w["name"]: w for w in MAN["workloads"]}
E2E = {m["name"]: m for m in MAN["end_to_end"]}


def reports(metric, cell):
    return cell in metric.get("workloads", [cell])


def test_top_level_keys():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert MAN["command"] == ["python3", "perfbench/run.py"]
    assert MAN["paths"] == ["perfbench"]
    assert isinstance(MAN["run_seconds"], int) and \
        1 <= MAN["run_seconds"] <= 51
    assert len(json.dumps(MAN)) < 64 * 1024


def test_names_and_units():
    for sec in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in MAN[sec]]
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names), names
    for w in MAN["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for m in MAN["end_to_end"] + MAN["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in MAN["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in MAN["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200


def test_every_metrics_move_is_reported_where_it_is_read():
    for m in MAN["per_layer"]:
        assert m["moves"] in E2E
        for cell in m.get("workloads", list(CELLS)):
            assert cell in CELLS
            assert reports(E2E[m["moves"]], cell), (m["name"], cell)


def test_every_cell_reports_setup_and_more():
    for cell in CELLS:
        got = [n for n, m in E2E.items() if reports(m, cell)]
        assert "setup_s" in got and len(got) >= 2
        assert any(reports(m, cell) for m in MAN["per_layer"])


def test_files_are_found_by_name():
    used = {w["config"] for w in MAN["workloads"]}
    for c in MAN["configs"]:
        assert c["name"] in used
        f = ROOT / c["file"]
        assert f.parts[len(ROOT.parts)] == "perfbench"
        conf = json.loads(f.read_text())
        assert conf["name"] == c["name"] and conf["source"] == c["source"]
        assert sorted(conf["reduced"]) == sorted(c["reduced"])
        for k in c["reduced"]:
            assert not re.search(r"(^hidden_size$|intermediate_size$|_dim$|"
                                 r"_rank$|head_size|expan|experts_per_tok)",
                                 k), k
    for name, w in CELLS.items():
        assert (HERE / "traffic" / f"{w['traffic']}.json").exists()
        assert (HERE / "cells" / f"{name}.json").exists()
    for m in MAN["per_layer"]:
        assert (HERE / "metrics" / f"{m['name']}.py").exists()


REASON = json.loads((HERE / "traffic" / "reason.json").read_text())


def test_arrival_rate_follows_the_formula():
    o = REASON["output"]
    outs = tr.lognormal_quantiles(4096, o["median"], o["sigma"], o["min"],
                                  o["max"])
    ticks = 1 + np.mean(np.ceil(outs / 8))
    assert math.isclose(tr.arrival_rate(REASON, 64, 8),
                        REASON["load"] * 64 / ticks)
    assert o["min"] <= outs.min() and outs.max() <= o["max"]


@pytest.mark.parametrize("seed", [0, 2 ** 31 + 12345])
def test_traffic_is_deterministic_per_seed(seed):
    a = tr.serve_requests(REASON, seed, 1000, 64, 8, 40)
    b = tr.serve_requests(REASON, seed, 1000, 64, 8, 40)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x[0] == y[0] and x[2] == y[2]
        np.testing.assert_array_equal(x[1], y[1])


def test_seeds_share_sizes_and_arrivals():
    a = tr.serve_requests(REASON, 1, 1000, 64, 8, 40)
    b = tr.serve_requests(REASON, 2, 1000, 64, 8, 40)
    assert [r[0] for r in a] == [r[0] for r in b]
    assert sorted(len(r[1]) for r in a) == sorted(len(r[1]) for r in b)
    assert sorted(r[2] for r in a) == sorted(r[2] for r in b)
    assert any(not np.array_equal(x[1], y[1]) for x, y in zip(a, b))
    assert sum(r[0] == 0 for r in a) == round(REASON["backlog"] * 64)


def test_command_fails_without_an_accelerator():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = MAN["workloads"][0]["name"]
    p = subprocess.run(MAN["command"] + ["--workload", cell, "--seed", "7",
                                         "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
