"""Plain references: float32, `highest` matmul precision, no kernels, no
cache, no batching tricks. Independent of `ray_tpu/models/`: they take
weights by the published parameter names, not the program's pytrees."""
