"""Scalability-envelope shapes at toy sizes (reference
release/benchmarks/README.md): many queued tasks, many actors, many
placement groups, a many-ref get and one large object read on every
node, each through the public API on a 4-node cluster. Each must
complete; none is timed.
"""

import os

import numpy as np

import ray_tpu
from ray_tpu.cluster_utils import Cluster
from ray_tpu.util.placement_group import (
    placement_group,
    remove_placement_group,
)
from ray_tpu.util.scheduling_strategies import NodeAffinitySchedulingStrategy


def test_envelope_smoke():
    ray_tpu.shutdown()
    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 4})
    for _ in range(3):
        cluster.add_node(num_cpus=4)
    cluster.wait_for_nodes()
    cluster.connect()
    try:
        @ray_tpu.remote
        def noop(i):
            return i

        # Many queued tasks: far beyond the 16 CPUs, then drained.
        assert ray_tpu.get([noop.remote(i) for i in range(60)],
                           timeout=300) == list(range(60))

        # One get over many refs that are all ready.
        refs = [noop.remote(i) for i in range(40)]
        ready, pending = ray_tpu.wait(refs, num_returns=40, timeout=300)
        assert len(ready) == 40 and not pending
        assert ray_tpu.get(refs, timeout=60) == list(range(40))

        # Many actors: create, one call each, kill.
        @ray_tpu.remote
        class A:
            def ping(self):
                return 1

        actors = [A.options(num_cpus=0.01).remote() for _ in range(4)]
        assert ray_tpu.get([a.ping.remote() for a in actors],
                           timeout=300) == [1] * 4
        for a in actors:
            ray_tpu.kill(a)

        # Many placement groups of one tiny bundle: create, ready, remove.
        pgs = [placement_group([{"CPU": 0.01}]) for _ in range(3)]
        for pg in pgs:
            pg.ready(timeout=120)  # raises unless every bundle committed
        for pg in pgs:
            remove_placement_group(pg)

        # Broadcast: one 8 MB object read by one task on every node.
        arr = np.random.default_rng(0).random(8 * 1024 * 1024 // 8)
        big = ray_tpu.put(arr)

        @ray_tpu.remote
        def read(x):
            return os.environ.get("RAY_TPU_NODE_ID"), float(x[::4096].sum())

        nodes = [n["NodeID"] for n in ray_tpu.nodes() if n["Alive"]]
        assert len(nodes) == 4
        got = ray_tpu.get([read.options(
            scheduling_strategy=NodeAffinitySchedulingStrategy(
                node_id=nid, soft=False)).remote(big) for nid in nodes],
            timeout=300)
        assert [nid for nid, _ in got] == nodes
        expect = float(arr[::4096].sum())
        assert all(abs(s - expect) < 1e-6 * max(1.0, abs(expect))
                   for _, s in got)
    finally:
        cluster.shutdown()
