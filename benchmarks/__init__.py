"""The benchmark: the yardstick of ray_tpu on the chip (see PERF.md).

Everything a number depends on lives here, where later PRs may add files
and not edit them: traffic generation (`traffic.py` + `traffic/*.json`),
the trace reduction (`xplane.py`), peaks and FLOP/byte arithmetic
(`peaks.py`), the plain references (`reference/`), one reader per
per-layer metric (`layer_metrics/`), and the builders that drive a
configuration through the system's entry points (`builders/`).
"""
