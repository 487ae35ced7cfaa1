from repro.kernels.tile_intervals.ops import tile_intervals  # noqa: F401
