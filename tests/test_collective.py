"""Host collective plane (`ray_tpu.collective`): ring/tree collectives over
the object-transfer plane, GCS group membership, rank-attributed aborts.

Most tests drive ranks as THREADS over an in-process multi-node Cluster
(RayletTransport — full GCS control plane + chunked transfer plane, no
worker processes); the runtime-transport path is covered with real rank
actors, joined through the reference's name (`ray_tpu.util.collective`).
"""

import threading
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu import collective
from ray_tpu.cluster_utils import Cluster
from ray_tpu.collective import CollectiveGroup, RayletTransport
from ray_tpu.collective.buffer import PackedTree, tree_index
from ray_tpu.core.config import GLOBAL_CONFIG
from ray_tpu.exceptions import CollectiveError

CHUNK = 256 * 1024
STALL_S = 10.0
WORLD = 4


@pytest.fixture()
def collective_cluster():
    """4 raylets, tiny chunks, short stall timeout; no driver session."""
    ray_tpu.shutdown()
    saved = dict(GLOBAL_CONFIG._overrides)
    GLOBAL_CONFIG._overrides.update({
        "object_transfer_chunk_bytes": CHUNK,
        "collective_stall_timeout_s": STALL_S,
        "collective_ring_min_bytes": 64 * 1024,
        "rpc_connect_timeout_s": 2.0,
    })
    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 1})
    for _ in range(WORLD - 1):
        cluster.add_node(num_cpus=1)
    cluster.wait_for_nodes()
    try:
        yield cluster
    finally:
        cluster.shutdown()
        GLOBAL_CONFIG._overrides.clear()
        GLOBAL_CONFIG._overrides.update(saved)


def _run_ranks(cluster, fn, world=WORLD, join_s=90.0):
    """fn(rank, group) on one thread per rank; returns (results, errors)."""
    results, errors = [None] * world, [None] * world

    def run(rank):
        try:
            group = CollectiveGroup(
                "t", world, rank,
                transport=RayletTransport(cluster.raylets[rank]))
            try:
                results[rank] = fn(rank, group)
            finally:
                if rank == 0:
                    group.destroy()
                else:
                    group.leave()
        except Exception as e:  # noqa: BLE001 — asserted by callers
            errors[rank] = e

    threads = [threading.Thread(target=run, args=(r,)) for r in range(world)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(join_s)
    return results, errors


def _group_record(cluster, name="t"):
    return cluster.raylets[0].gcs.call("collective_get", {"name": name})


# --------------------------------------------------------------------------- #
# Numeric parity
# --------------------------------------------------------------------------- #


def test_ring_allreduce_matches_numpy_on_pytrees(collective_cluster):
    """Ring allreduce (payload >> ring threshold) of a mixed-dtype pytree
    equals the numpy reference on every rank, for sum/max/mean."""
    rng = np.random.default_rng(7)
    values = [{"w": rng.standard_normal((1000, 200)).astype(np.float32),
               "b": rng.standard_normal(17),
               "step": np.int64(i + 1),
               "nested": [rng.standard_normal(63).astype(np.float32)]}
              for i in range(WORLD)]

    def fn(rank, group):
        return {"sum": group.allreduce(values[rank], op="sum"),
                "max": group.allreduce(values[rank], op="max"),
                "mean": group.allreduce(values[rank], op="mean")}

    results, errors = _run_ranks(collective_cluster, fn)
    assert not any(errors), errors
    want_w = sum(v["w"] for v in values)
    want_b = sum(v["b"] for v in values)
    max_w = np.maximum.reduce([v["w"] for v in values])
    for out in results:
        np.testing.assert_allclose(out["sum"]["w"], want_w, atol=1e-4)
        np.testing.assert_allclose(out["sum"]["b"], want_b, rtol=1e-12)
        assert int(out["sum"]["step"]) == sum(range(1, WORLD + 1))
        np.testing.assert_array_equal(out["max"]["w"], max_w)
        np.testing.assert_allclose(out["mean"]["w"], want_w / WORLD,
                                   atol=1e-4)
        np.testing.assert_allclose(
            out["sum"]["nested"][0],
            sum(v["nested"][0] for v in values), atol=1e-4)
    # Identical results on every rank, bit for bit (they all hold the same
    # reduced segments after the all-gather phase).
    for out in results[1:]:
        np.testing.assert_array_equal(out["sum"]["w"], results[0]["sum"]["w"])


def test_small_payload_inline_path_and_mailbox_drains(collective_cluster):
    """Tiny payloads ride the GCS mailbox inline (fan-in path, no store
    objects); the refcounted mailbox is empty after every op."""
    def fn(rank, group):
        out = group.allreduce({"loss": float(rank), "n": np.int64(rank)})
        # Every allreduce (fan-in included) ends with a group sync, so all
        # takes have drained by the time any rank returns. The barrier
        # below fences the record check against a faster rank's teardown
        # (leave/destroy would GC the record under us).
        rec = _group_record(collective_cluster)
        assert rec["known"] and rec["mailbox_keys"] == 0, rec
        group.barrier()
        return out

    results, errors = _run_ranks(collective_cluster, fn)
    assert not any(errors), errors
    for out in results:
        assert float(out["loss"]) == sum(range(WORLD))
    # Graceful leave of every member GC'd the record.
    assert _group_record(collective_cluster) == {"known": False}


def test_allgather_broadcast_reducescatter(collective_cluster):
    rng = np.random.default_rng(3)
    big = rng.integers(0, 255, size=3 * CHUNK + 123,
                       dtype=np.uint8)  # multi-chunk broadcast payload

    def fn(rank, group):
        gathered = group.allgather({"rank": rank})
        bcast = group.broadcast(big if rank == 2 else None, src_rank=2)
        rows = group.reducescatter(
            np.full((WORLD * 3, 5), float(rank), dtype=np.float64))
        return gathered, bcast, rows

    results, errors = _run_ranks(collective_cluster, fn)
    assert not any(errors), errors
    want_rows = np.full((3, 5), float(sum(range(WORLD))))
    for rank, (gathered, bcast, rows) in enumerate(results):
        assert [g["rank"] for g in gathered] == list(range(WORLD))
        np.testing.assert_array_equal(np.asarray(bcast), big)
        np.testing.assert_array_equal(rows, want_rows)


def test_reducescatter_remainder_raises(collective_cluster):
    """shape[0] % world_size != 0 must raise a clear ValueError, not
    silently drop the remainder rows (regression)."""
    def fn(rank, group):
        with pytest.raises(ValueError, match="not divisible"):
            group.reducescatter(np.ones((WORLD * 3 + 1, 4)))
        return True

    results, errors = _run_ranks(collective_cluster, fn)
    assert not any(errors), errors
    assert all(results)
    # The same validation, directly on the helper.
    with pytest.raises(ValueError, match="not divisible"):
        tree_index({"x": np.ones((5, 2))}, rank=0, world=4)


def test_packed_tree_roundtrip_unit():
    """Packing layer alone: mixed dtypes, padding, segment reduce."""
    value = {"a": np.arange(10, dtype=np.float32).reshape(2, 5),
             "b": [np.float64(2.5), np.arange(3, dtype=np.int64)]}
    packed = PackedTree(value, segments=4)
    out = packed.unpack()
    np.testing.assert_array_equal(out["a"], value["a"])
    assert float(out["b"][0]) == 2.5
    np.testing.assert_array_equal(out["b"][1], value["b"][1])
    other = PackedTree(value, segments=4)
    for s in range(4):
        joined = b"".join(bytes(p) for p in other.segment_parts(s))
        packed.reduce_segment(s, joined, np.add)
    doubled = packed.unpack()
    np.testing.assert_array_equal(doubled["a"], value["a"] * 2)


# --------------------------------------------------------------------------- #
# Membership validation
# --------------------------------------------------------------------------- #


def test_world_size_mismatch_raises(collective_cluster):
    cluster = collective_cluster
    CollectiveGroup("m", 4, 0, transport=RayletTransport(cluster.raylets[0]))
    with pytest.raises(ValueError, match="world_size=4"):
        CollectiveGroup("m", 3, 1,
                        transport=RayletTransport(cluster.raylets[1]))


def test_rank_taken_and_rejoin_after_destroy(collective_cluster):
    cluster = collective_cluster
    g0 = CollectiveGroup("m", 4, 0,
                         transport=RayletTransport(cluster.raylets[0]))
    with pytest.raises(ValueError, match="already held"):
        CollectiveGroup("m", 4, 0,
                        transport=RayletTransport(cluster.raylets[1]))
    g0.destroy()
    # Fresh epoch: the name is reusable, even with a different world size.
    g1 = CollectiveGroup("m", 2, 0,
                         transport=RayletTransport(cluster.raylets[1]))
    assert g1.epoch > g0.epoch


# --------------------------------------------------------------------------- #
# Failure semantics
# --------------------------------------------------------------------------- #


def test_member_death_aborts_survivors_with_rank(collective_cluster):
    """Killing one member's node mid-op makes every surviving rank raise a
    CollectiveError naming the dead rank, well inside the stall timeout —
    never a 300s hang."""
    cluster = collective_cluster
    payload = np.ones(2 * CHUNK, dtype=np.float32)
    round_one = threading.Barrier(WORLD, timeout=60)
    errors = [None] * WORLD
    abort_s = [None] * WORLD

    def run(rank):
        try:
            group = CollectiveGroup(
                "d", WORLD, rank,
                transport=RayletTransport(cluster.raylets[rank]))
            group.allreduce(payload)
            round_one.wait()
            if rank == 3:
                return  # goes silent; its raylet is killed below
            t0 = time.monotonic()
            try:
                group.allreduce(payload)
            finally:
                abort_s[rank] = time.monotonic() - t0
        except Exception as e:  # noqa: BLE001
            errors[rank] = e

    threads = [threading.Thread(target=run, args=(r,)) for r in range(WORLD)]
    for t in threads:
        t.start()
    threads[3].join(60)
    time.sleep(0.3)  # survivors are now parked inside round 2
    cluster.remove_node(cluster.raylets[3])
    for t in threads[:3]:
        t.join(60)

    for rank in range(3):
        err = errors[rank]
        assert isinstance(err, CollectiveError), (rank, err)
        assert "rank 3" in str(err), err
        assert 3 in err.dead_ranks, err.dead_ranks
        assert abort_s[rank] < STALL_S, (
            f"rank {rank} took {abort_s[rank]:.1f}s to abort — the death "
            "push did not fire, only the stall timeout would have")


def test_barrier_reusable_across_rounds(collective_cluster):
    """Three barrier rounds on one group, with a straggler each round:
    nobody leaves a barrier before the straggler arrives, and the per-seq
    barrier state is GC'd after each round."""
    crossings = []
    lock = threading.Lock()
    # `_run_ranks` destroys the group the moment rank 0's fn returns: every
    # rank must have read the group's record by then, whichever thread the
    # scheduler runs first (rank 0 used to win, and the others read a
    # record that was gone).
    all_read = threading.Barrier(WORLD)

    def fn(rank, group):
        for rnd in range(3):
            if rank == rnd:  # a different straggler each round
                time.sleep(0.4)
                with lock:
                    crossings.append(("late", rnd, rank))
            group.barrier()
            with lock:
                crossings.append(("crossed", rnd, rank))
        try:
            rec = _group_record(collective_cluster)
        finally:
            all_read.wait(30)
        assert rec["pending_barriers"] == 0, rec
        return True

    results, errors = _run_ranks(collective_cluster, fn)
    assert not any(errors), errors
    assert all(results)
    for rnd in range(3):
        late = crossings.index(("late", rnd, rnd))
        first_cross = min(i for i, c in enumerate(crossings)
                          if c[0] == "crossed" and c[1] == rnd)
        assert late < first_cross, (
            f"round {rnd}: a rank crossed the barrier before the "
            f"straggler arrived: {crossings}")


# --------------------------------------------------------------------------- #
# Runtime transport (real rank actors)
# --------------------------------------------------------------------------- #


class _RankActor:
    def __init__(self, rank, world, group_name="actors"):
        from ray_tpu.util.collective import init_collective_group

        self.group = init_collective_group(world, rank,
                                           group_name=group_name)

    def allreduce_value(self, value):
        return self.group.allreduce(value)


def test_runtime_transport_actors_and_death(collective_cluster):
    """Worker-process ranks over the runtime transport: results match, and
    killing one member's process aborts the peer with the dead rank —
    membership fate-shares with the worker's GCS connection."""
    cluster = collective_cluster
    cluster.connect()
    actor_cls = ray_tpu.remote(_RankActor)
    a0 = actor_cls.options(max_concurrency=2).remote(0, 2)
    a1 = actor_cls.options(max_concurrency=2).remote(1, 2)
    arr = np.arange(CHUNK, dtype=np.float64)  # > inline, exercises the store
    r0 = a0.allreduce_value.remote({"g": arr})
    r1 = a1.allreduce_value.remote({"g": arr * 2})
    out0, out1 = ray_tpu.get([r0, r1], timeout=120)
    np.testing.assert_allclose(np.asarray(out0["g"]), arr * 3)
    np.testing.assert_allclose(np.asarray(out1["g"]), arr * 3)

    pending = a0.allreduce_value.remote({"g": arr})  # a1 never joins this op
    time.sleep(0.3)
    ray_tpu.kill(a1)
    with pytest.raises(CollectiveError, match="rank 1"):
        ray_tpu.get(pending, timeout=60)
