"""Host-level collective ops under the reference's public name.

`ray.util.collective` is where the reference keeps this API, so
`ray_tpu.util.collective` exists for code written against that name. The
implementation lives in `ray_tpu.collective` (ring allreduce / tree
broadcast over the pipelined object-transfer plane, GCS-backed membership
with rank-attributed death aborts: docs/COLLECTIVE.md); the names below
are that package's own functions, one registry of groups per process.
"""

from ray_tpu.collective import (  # noqa: F401
    allgather,
    allreduce,
    barrier,
    broadcast,
    destroy_collective_group,
    get_group,
    init_collective_group,
    reducescatter,
)
