from repro.kernels.text_probe.ops import (  # noqa: F401
    text_probe_pruned,
)
