from repro.distributed import compression, pipeline, sharding
