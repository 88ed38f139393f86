"""Streaming ingest plane under its own clusters: spill through a tiny
arena, chaos node-kill recovery. Separate module: these tests build (and
tear down) dedicated clusters and must not share a module-scoped one."""

import gc
import glob
import os
import time

import pytest

from ray_tpu import data as rd


# --------------------------------------------------------------------------- #
# Spill tier under a tiny arena
# --------------------------------------------------------------------------- #


def _shm_segments(session_suffix: str):
    """Live (non-pool, non-staging) store segments of this session."""
    return [p for p in glob.glob(f"/dev/shm/rtpu_{session_suffix}_*")
            if "_pool" not in os.path.basename(p)]


def test_full_shuffle_epoch_spills_not_oom():
    """A shuffle whose working set exceeds a tiny store arena completes
    via the spill tier: full epoch, rows exact, `num_unsealed == 0`, and
    zero leaked segments after the refs drop."""
    import ray_tpu

    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4, object_store_memory=3 * 1024 * 1024)
    try:
        node = ray_tpu._global_node
        store = node.raylet.store
        # Blocks must clear the inline threshold (100 KiB) or they never
        # touch the store: 4 blocks x ~1 MiB of tensor rows, working set
        # (inputs + buckets + outputs) ~3x the 3 MiB arena.
        ds = rd.range_tensor(8000, shape=(16,), parallelism=4) \
            .random_shuffle(seed=2)
        total = 0
        for batch in ds.iter_batches(batch_size=500):
            total += len(batch["data"])
        assert total == 8000
        stats = store.stats()
        assert stats["num_unsealed"] == 0, stats
        # The arena could not have held the epoch: spill carried it.
        assert stats["used_bytes"] <= store.capacity
        # Drop the pipeline; every segment must drain (frees are batched
        # on a 1s timer, so poll with a deadline).
        del ds
        gc.collect()
        deadline = time.monotonic() + 15
        session = node.session_suffix
        while time.monotonic() < deadline:
            if not _shm_segments(session) and \
                    store.stats()["num_unsealed"] == 0:
                break
            time.sleep(0.2)
        leaked = _shm_segments(session)
        assert not leaked, f"leaked segments: {leaked}"
    finally:
        ray_tpu.shutdown()


@pytest.mark.slow  # multi-node cluster + recovery: >10s under load
def test_node_death_mid_shuffle_recomputes_bounded():
    """Chaos: kill a node mid-shuffle. The epoch completes, the kill
    destroyed blocks the pipeline still needed (recomputed >= 1),
    recomputed blocks are bounded by the dead node's resident blocks
    (never a whole-pipeline restart), and nothing hangs."""
    import ray_tpu
    from ray_tpu.chaos import HangWatchdog
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.data.context import DataContext
    from ray_tpu.data.streaming.lineage import core_reconstructions

    ray_tpu.shutdown()
    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 2})
    # Pipeline tasks pin to the killable nodes through the churn
    # resource: the head is never the victim, so a kill must hit blocks
    # the pipeline holds for the recompute bound to mean something.
    for _ in range(2):
        cluster.add_node(num_cpus=2, resources={"churn": 2})
    cluster.wait_for_nodes()
    cluster.connect()
    ctx = DataContext.get_current()
    old_in_flight = ctx.max_tasks_in_flight_per_op
    # Two reduces in flight: the kill lands while most partitions still
    # need their buckets, not after a fast exchange has finished.
    ctx.max_tasks_in_flight_per_op = 2
    try:
        # Few fat partitions: inputs ~1 MiB, buckets ~128 KiB, so every
        # block clears the 100 KiB inline threshold and lives in a node's
        # store (inline blocks live in the GCS and survive any kill).
        n_rows, n_parts = 16_000, 8
        ds = rd.range_tensor(n_rows, shape=(64,), parallelism=n_parts) \
            .with_resources(resources={"churn": 0.25}) \
            .random_shuffle(seed=9)
        base = core_reconstructions()
        rows = 0
        killed = {}
        with HangWatchdog(limit_s=90.0) as wd:
            for batch in ds.iter_batches(batch_size=512):
                rows += len(batch["data"])
                if not killed:
                    # Kill the worker node holding the most blocks so the
                    # fault actually destroys state the pipeline needs.
                    victim = max(
                        (r for r in cluster.raylets if not r.is_head),
                        key=lambda r: r.store.stats()["num_objects"])
                    killed["resident"] = \
                        victim.store.stats()["num_objects"]
                    cluster.crash_node(victim)
                    cluster.add_node(num_cpus=2, resources={"churn": 2})
        wd.assert_no_hangs()
        assert rows == n_rows
        recomputed = (core_reconstructions() - base) \
            + (ds._lineage.recomputed_blocks if ds._lineage else 0)
        assert recomputed >= 1, "the kill destroyed nothing the shuffle used"
        # Bounded: no more re-executions than the victim held blocks
        # (map buckets + reduce outputs) plus one resubmission per output
        # partition, and certainly not a restart of every task.
        assert recomputed <= max(killed["resident"], 1) + n_parts, \
            (recomputed, killed)
        for raylet in cluster.raylets:
            assert raylet.store.stats()["num_unsealed"] == 0
    finally:
        ctx.max_tasks_in_flight_per_op = old_in_flight
        try:
            cluster.shutdown()
        except Exception:  # noqa: BLE001 — nodes already churned
            pass
