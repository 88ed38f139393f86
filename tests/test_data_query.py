"""Distributed query tier: locality-routed sort/groupby/join over the
streaming plane (ray_tpu/data/query/), per-tenant data budgets, and the
same-host sealed-segment attach fast path.

Row-identity discipline: every operator's output is compared against a
driver-side reference computed from the same input rows — across seeds,
partition counts, and both join strategies — while the driver-resident
state stays bounded (asserted via `last_sort_stats`).
"""

import threading
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu import data as rd
from ray_tpu.core.config import GLOBAL_CONFIG
from ray_tpu.data.context import DataContext


# --------------------------------------------------------------------------- #
# Distributed sort
# --------------------------------------------------------------------------- #


def _ref_sort(rows, key, descending=False):
    """Driver-side stable reference (what the distributed sort must
    reproduce row-for-row)."""
    keyf = key if callable(key) else (lambda r: r[key])
    return sorted(rows, key=keyf, reverse=descending)


@pytest.mark.parametrize("parallelism", [1, 3, 7])
@pytest.mark.parametrize("seed", [0, 11])
def test_sort_row_identity_across_partition_counts(ray_start_shared,
                                                   parallelism, seed):
    rng = np.random.default_rng(seed)
    rows = [{"k": int(rng.integers(0, 20)), "v": i} for i in range(200)]
    ds = rd.from_items(rows, parallelism=parallelism).sort(key="k")
    got = ds.take_all()
    # Stable: equal keys keep input order — byte-for-byte row identity,
    # not just key order.
    assert got == _ref_sort(rows, "k")


def test_sort_descending_is_stable(ray_start_shared):
    rows = [{"k": i % 5, "v": i} for i in range(100)]
    ds = rd.from_items(rows, parallelism=4).sort(key="k", descending=True)
    assert ds.take_all() == _ref_sort(rows, "k", descending=True)


def test_sort_callable_key_and_plain_values(ray_start_shared):
    vals = [7, 3, 9, 1, 3, 8, 0, 5]
    ds = rd.from_items(vals, parallelism=3).sort(key=lambda x: -x)
    assert ds.take_all() == sorted(vals, reverse=True)
    # Plain comparable values need no key at all.
    assert rd.from_items(vals, parallelism=2).sort().take_all() == \
        sorted(vals)


def test_sort_string_keys_columnar_path(ray_start_shared):
    rows = [{"k": f"key-{i % 7:02d}", "v": i} for i in range(80)]
    ds = rd.from_items(rows, parallelism=4).sort(key="k")
    assert ds.take_all() == _ref_sort(rows, "k")


def test_sort_single_key_and_skew(ray_start_shared):
    # All-equal keys: one range partition swallows everything; output is
    # the input (stability) regardless of boundary degeneracy.
    rows = [{"k": 1, "v": i} for i in range(60)]
    assert rd.from_items(rows, parallelism=4).sort(key="k").take_all() \
        == rows
    # 90% of rows share one key: the skewed partition still sorts
    # correctly and equal keys never split across partitions.
    rng = np.random.default_rng(3)
    skewed = [{"k": 5 if rng.random() < 0.9 else int(rng.integers(0, 100)),
               "v": i} for i in range(300)]
    got = rd.from_items(skewed, parallelism=5).sort(key="k").take_all()
    assert got == _ref_sort(skewed, "k")


def test_sort_empty_dataset(ray_start_shared):
    assert rd.from_items([{"k": 1}]).filter(lambda r: False) \
        .sort(key="k").take_all() == []


def test_sort_driver_sample_bytes_bounded(ray_start_shared):
    """The driver's entire per-row footprint is the boundary sample —
    bounded by `query_sort_sample_rows`, measured and asserted, and the
    output is STILL row-identical (equal keys never split, local sorts
    are stable, so any sample draw yields the same global order)."""
    ctx = DataContext.get_current()
    old = ctx.sort_sample_rows
    try:
        ctx.sort_sample_rows = 32
        rows = [{"k": int(np.random.default_rng(9).integers(0, 50)),
                 "v": i} for i in range(5000)]
        ds = rd.from_items(rows, parallelism=8).sort(key="k")
        got = ds.take_all()
        assert got == _ref_sort(rows, "k")
        stats = ds.last_sort_stats
        assert 0 < stats["sample_rows"] <= 32
        # 32 int keys serialize well under this; 5000 rows would not.
        assert stats["driver_sample_bytes"] < 16 * 1024
        assert ds.last_shuffle_stats["input_blocks"] == 8
    finally:
        ctx.sort_sample_rows = old


def test_sort_chains_with_downstream_transforms(ray_start_shared):
    rows = [{"k": i % 4, "v": i} for i in range(40)]
    ds = rd.from_items(rows, parallelism=4).sort(key="k") \
        .map(lambda r: {"k": r["k"], "v2": r["v"] * 2})
    got = ds.take_all()
    assert got == [{"k": r["k"], "v2": r["v"] * 2}
                   for r in _ref_sort(rows, "k")]


# --------------------------------------------------------------------------- #
# Distributed groupby
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("parallelism", [1, 4, 9])
def test_groupby_aggregate_matches_reference(ray_start_shared,
                                             parallelism):
    rng = np.random.default_rng(parallelism)
    rows = [{"g": int(rng.integers(0, 12)), "x": float(rng.normal())}
            for _ in range(300)]
    ds = rd.from_items(rows, parallelism=parallelism)
    got = {r["g"]: r for r in ds.groupby("g").sum("x").take_all()}
    keys = sorted({r["g"] for r in rows})
    assert sorted(got) == keys
    for k in keys:
        want = sum(r["x"] for r in rows if r["g"] == k)
        assert got[k]["sum(x)"] == pytest.approx(want)


def test_groupby_multi_aggregate_single_pass(ray_start_shared):
    from ray_tpu.data.query import Count, Max, Mean, Min, Sum

    rows = [{"g": i % 3, "x": i} for i in range(30)]
    out = rd.from_items(rows, parallelism=4).groupby("g").aggregate(
        Count(), Sum("x"), Mean("x"), Min("x"), Max("x")).take_all()
    assert [r["g"] for r in out] == [0, 1, 2]
    for r in out:
        vals = [row["x"] for row in rows if row["g"] == r["g"]]
        assert r["count()"] == len(vals)
        assert r["sum(x)"] == sum(vals)
        assert r["mean(x)"] == pytest.approx(sum(vals) / len(vals))
        assert r["min(x)"] == min(vals)
        assert r["max(x)"] == max(vals)


def test_groupby_custom_aggregate_fn(ray_start_shared):
    from ray_tpu.data.query import AggregateFn

    # Sum of squares as a UDF: init/accumulate/merge/finalize compose
    # through partial pre-aggregation exactly like the built-ins.
    sumsq = AggregateFn(
        init=lambda: 0.0,
        accumulate=lambda s, row: s + row["x"] ** 2,
        merge=lambda a, b: a + b,
        name="sumsq(x)")
    rows = [{"g": i % 4, "x": i} for i in range(40)]
    out = rd.from_items(rows, parallelism=5).groupby("g") \
        .aggregate(sumsq).take_all()
    for r in out:
        want = sum(row["x"] ** 2 for row in rows if row["g"] == r["g"])
        assert r["sumsq(x)"] == pytest.approx(want)


def test_groupby_single_key_and_empty(ray_start_shared):
    rows = [{"g": "only", "x": i} for i in range(25)]
    out = rd.from_items(rows, parallelism=4).groupby("g").count() \
        .take_all()
    assert out == [{"g": "only", "count()": 25}]
    empty = rd.from_items(rows).filter(lambda r: False) \
        .groupby("g").count().take_all()
    assert empty == []


# --------------------------------------------------------------------------- #
# Distributed join
# --------------------------------------------------------------------------- #


def _ref_join(left, right, left_on, right_on, how):
    """Driver-side nested-loop reference with the zip() `_1` collision
    suffix contract."""
    out = []
    rcols = []
    for rrow in right:
        for c in rrow:
            if c not in rcols:
                rcols.append(c)
    for lrow in left:
        matches = [r for r in right if r[right_on] == lrow[left_on]]
        if not matches and how == "left":
            row = dict(lrow)
            for c in rcols:
                if c != right_on:
                    row[c + "_1" if c in lrow else c] = None
            out.append(row)
        for rrow in matches:
            row = dict(lrow)
            for c, v in rrow.items():
                if c == right_on:
                    continue
                row[c + "_1" if c in lrow else c] = v
            out.append(row)
    return out


def _rows_set(rows):
    return sorted(tuple(sorted(r.items())) for r in rows)


@pytest.mark.parametrize("how", ["inner", "left"])
def test_join_hash_and_broadcast_row_identity(ray_start_shared, how):
    left = [{"id": i % 6, "lv": i} for i in range(40)]
    # Duplicate build keys (cartesian per key) + a key with no probe
    # match + a colliding non-key column name.
    right = [{"id": 0, "rv": 100, "lv": -1}, {"id": 0, "rv": 101},
             {"id": 2, "rv": 102}, {"id": 99, "rv": 103}]
    want = _rows_set(_ref_join(left, right, "id", "id", how))
    ctx = DataContext.get_current()
    old = ctx.broadcast_join_bytes
    try:
        lds = rd.from_items(left, parallelism=4)
        rds = rd.from_items(right, parallelism=2)
        ctx.broadcast_join_bytes = 1 << 30
        bds = lds.join(rds, on="id", how=how)
        assert _rows_set(bds.take_all()) == want
        assert bds.last_join_stats["strategy"] == "broadcast"

        ctx.broadcast_join_bytes = 0
        hds = lds.join(rds, on="id", how=how)
        assert _rows_set(hds.take_all()) == want
        assert hds.last_join_stats["strategy"] == "hash"
        assert hds.last_join_stats["left_shuffle"]["input_blocks"] > 0
    finally:
        ctx.broadcast_join_bytes = old


def test_join_build_side_exactly_at_threshold(ray_start_shared):
    """The strategy flips exactly at `query_broadcast_join_bytes`: a
    build side AT the threshold broadcasts, one byte under it forces the
    hash exchange — and both produce identical rows."""
    left = [{"id": i % 8, "lv": i} for i in range(64)]
    right = [{"id": i, "rv": i * 10} for i in range(8)]
    lds = rd.from_items(left, parallelism=4)
    rds = rd.from_items(right, parallelism=2)
    ctx = DataContext.get_current()
    old = ctx.broadcast_join_bytes
    try:
        probe = lds.join(rds, on="id")
        want = _rows_set(probe.take_all())
        build_bytes = probe.last_join_stats["build_bytes"]
        assert build_bytes > 0

        ctx.broadcast_join_bytes = build_bytes
        at = lds.join(rds, on="id")
        assert _rows_set(at.take_all()) == want
        assert at.last_join_stats["strategy"] == "broadcast"

        ctx.broadcast_join_bytes = build_bytes - 1
        under = lds.join(rds, on="id")
        assert _rows_set(under.take_all()) == want
        assert under.last_join_stats["strategy"] == "hash"
    finally:
        ctx.broadcast_join_bytes = old


def test_join_tuple_on_and_validation(ray_start_shared):
    left = [{"lid": i, "a": i * 2} for i in range(6)]
    right = [{"rid": i, "b": i * 3} for i in range(0, 12, 2)]
    out = rd.from_items(left, parallelism=2).join(
        rd.from_items(right, parallelism=2), on=("lid", "rid")) \
        .take_all()
    assert _rows_set(out) == _rows_set(
        _ref_join(left, right, "lid", "rid", "inner"))
    with pytest.raises(ValueError):
        rd.from_items(left).join(rd.from_items(right), on=("lid",))
    with pytest.raises(ValueError):
        rd.from_items(left).join(rd.from_items(right), on="lid",
                                 how="outer")


def test_join_empty_sides(ray_start_shared):
    left = [{"id": i} for i in range(5)]
    none = rd.from_items(left).filter(lambda r: False)
    assert rd.from_items(left).join(none, on="id").take_all() == []
    got = none.join(rd.from_items(left), on="id", how="inner").take_all()
    assert got == []


# --------------------------------------------------------------------------- #
# Per-tenant data budgets
# --------------------------------------------------------------------------- #


@pytest.fixture()
def tenant_cap():
    from ray_tpu.data.streaming.budget import reset_tenant_stats

    ctx = DataContext.get_current()
    old_tenant = ctx.tenant
    GLOBAL_CONFIG._overrides["data_tenant_budget_bytes"] = 100
    reset_tenant_stats()
    try:
        yield ctx
    finally:
        ctx.tenant = old_tenant
        GLOBAL_CONFIG._overrides.pop("data_tenant_budget_bytes", None)
        reset_tenant_stats()


def test_tenant_cap_rejects_with_backpressure(tenant_cap):
    """Admission past the tenant cap is refused (visible in
    `tenant_stats`), spanning BUDGETS: two pipelines of one tenant share
    the cap even though each is under its own pipeline budget."""
    from ray_tpu.data.streaming.budget import ByteBudget, tenant_stats

    tenant_cap.tenant = "tenant-a"
    a, b = ByteBudget(10_000), ByteBudget(10_000)
    assert a.try_acquire("map", 80)
    assert b.try_acquire("map", 15)  # 95 in flight: still under the cap
    assert not b.try_acquire("map", 50)  # would cross 100: refused
    st = tenant_stats()["tenant-a"]
    assert st["rejections"] >= 1
    assert st["bytes_in_flight"] == 95
    # Releasing in ONE budget unblocks the OTHER (same tenant).
    a.release("map", 80)
    assert b.try_acquire("map", 50)
    assert tenant_stats()["tenant-a"]["bytes_in_flight"] == 65


def test_tenant_progress_guarantee_never_deadlocks(tenant_cap):
    """A tenant with nothing in flight is ALWAYS admitted (even over the
    cap) — mirrors the per-op progress guarantee, so one oversized block
    degrades to window-at-a-time instead of wedging the pipeline."""
    from ray_tpu.data.streaming.budget import ByteBudget

    tenant_cap.tenant = "tenant-big"
    b = ByteBudget(10_000)
    assert b.try_acquire("map", 5_000)  # 50x the cap: idle tenant admits
    assert not b.try_acquire("map", 1)  # now it waits like everyone
    b.release("map", 5_000)
    assert b.try_acquire("map", 1)


def test_tenant_blocking_acquire_wakes_on_cross_budget_release(tenant_cap):
    from ray_tpu.data.streaming.budget import ByteBudget

    tenant_cap.tenant = "tenant-w"
    a, b = ByteBudget(10_000), ByteBudget(10_000)
    assert a.try_acquire("map", 90)
    done = []

    def blocked():
        done.append(b.acquire("map", 90, timeout=10.0))

    assert b.try_acquire("map", 5)  # b must have in-flight bytes to wait
    t = threading.Thread(target=blocked, daemon=True)
    t.start()
    time.sleep(0.1)
    a.release("map", 90)  # cross-budget release, observed via the poll
    t.join(timeout=10.0)
    assert done == [True]


def test_tenant_resolution_defaults(tenant_cap, monkeypatch):
    from ray_tpu.data.streaming.budget import ByteBudget

    monkeypatch.delenv("RAY_TPU_JOB_ID", raising=False)
    tenant_cap.tenant = None
    assert ByteBudget(10).tenant == "default"
    monkeypatch.setenv("RAY_TPU_JOB_ID", "job-42")
    assert ByteBudget(10).tenant == "job-42"
    tenant_cap.tenant = "explicit"
    assert ByteBudget(10).tenant == "explicit"


def test_tenant_cap_off_by_default(tenant_cap):
    from ray_tpu.data.streaming.budget import ByteBudget, tenant_stats

    GLOBAL_CONFIG._overrides["data_tenant_budget_bytes"] = 0
    tenant_cap.tenant = "tenant-free"
    b = ByteBudget(10_000)
    assert b.try_acquire("map", 4_000)
    assert b.try_acquire("map", 4_000)  # no cap: only the budget gates
    # Bytes still tracked for observability even with the cap off.
    assert tenant_stats()["tenant-free"]["bytes_in_flight"] == 8_000


# --------------------------------------------------------------------------- #
# Locality-routed split handout
# --------------------------------------------------------------------------- #


def test_iter_shards_locality_hit_accounting(ray_start_shared):
    """Single-node cluster, blocks past the 100 KiB inline threshold:
    every block the coordinator hands out is resident on the consumer's
    node, so the ingest stats must show hits and zero misses — and with
    routing off, the same handouts all count as misses."""
    ctx = DataContext.get_current()
    old = ctx.locality_routing
    try:
        # 4 blocks x 500 rows x 32 float64 = ~128 KiB each: real store
        # residency (inline blocks have no directory entry and would
        # honestly count as misses).
        ds = rd.range_tensor(2000, shape=(32,), parallelism=4) \
            .materialize()
        ctx.locality_routing = True
        shard, = rd.DataIterator(ds).iter_shards(1, prefetch=0)
        rows = sum(len(b["data"]) for b in shard.iter_batches(
            batch_size=500))
        assert rows == 2000
        stats = shard.ingest_stats()
        assert stats["locality_hits"] == 4
        assert stats["locality_misses"] == 0

        ctx.locality_routing = False
        shard2, = rd.DataIterator(ds).iter_shards(1, prefetch=0)
        rows = sum(len(b["data"]) for b in shard2.iter_batches(
            batch_size=500))
        assert rows == 2000
        stats2 = shard2.ingest_stats()
        assert stats2["locality_hits"] == 0
        assert stats2["locality_misses"] == 4
    finally:
        ctx.locality_routing = old


def test_split_coordinator_locality_never_starves(ray_start_shared):
    """Locality reorders the handout but every split still gets blocks
    and every block is handed out exactly once."""
    ds = rd.range_tensor(2000, shape=(32,), parallelism=4).materialize()
    it_a, it_b = ds.streaming_split(2)
    got_a = [b["data"].sum() for b in it_a.iter_batches(batch_size=500)]
    got_b = [b["data"].sum() for b in it_b.iter_batches(batch_size=500)]
    assert len(got_a) + len(got_b) == 4
    la, lb = it_a.locality_stats(), it_b.locality_stats()
    handed = (la["locality_hits"] + la["locality_misses"]
              + lb["locality_hits"] + lb["locality_misses"])
    assert handed == 4


class _ShardConsumer:
    def take_blocks(self, shard, routing, n_blocks):
        # The knob is resolved on the consumer's side (this process).
        DataContext.get_current().locality_routing = bool(routing)
        rows = 0
        batches = shard.iter_batches(batch_size=2000)   # one block each
        for _ in range(n_blocks):
            rows += len(next(batches)["data"])
        stats = shard.ingest_stats()
        return rows, stats["locality_hits"], stats["locality_misses"]


def test_locality_routing_moves_fewer_cross_node_bytes():
    """Eight blocks, four on each of two nodes and interleaved in the
    handout order; a consumer on each node takes four, one after the
    other. Routed, each is handed the blocks its own node holds and the
    raylets serve nothing over the socket; unrouted, the handout is
    FIFO, half of each consumer's blocks live on the other node, and
    they cross (the same-host attach is off here, or it would hide the
    bytes being counted)."""
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.data.dataset import Dataset

    ray_tpu.shutdown()
    saved = dict(GLOBAL_CONFIG._overrides)
    GLOBAL_CONFIG._overrides["object_transfer_same_host_attach"] = False
    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 1})
    for i in range(2):
        cluster.add_node(num_cpus=2, resources={f"pin{i}": 1})
    cluster.wait_for_nodes()
    cluster.connect()
    try:
        # 512 KiB blocks: real store residency with directory entries
        # (inline blocks live nowhere and cannot be routed to).
        @ray_tpu.remote(num_cpus=1)
        def make_block(tag):
            return {"data": np.full((2000, 32), float(tag))}

        refs = [make_block.options(resources={f"pin{i}": 0.01}).remote(
            2 * j + i) for j in range(4) for i in range(2)]
        ready, _ = ray_tpu.wait(refs, num_returns=len(refs), timeout=120)
        assert len(ready) == len(refs)
        consumer = ray_tpu.remote(num_cpus=1)(_ShardConsumer)

        def run_arm(routing):
            # A materialized dataset: the refs handed out ARE the pinned
            # blocks, not the outputs of a pass over them.
            ds = Dataset([(None, (r,)) for r in refs])
            ds._materialized_refs = refs
            shards = rd.DataIterator(ds).iter_shards(2, prefetch=0)
            served0 = sum(r._chunk_bytes_served for r in cluster.raylets)
            hits = 0
            for i, shard in enumerate(shards):
                actor = consumer.options(resources={f"pin{i}": 1}).remote()
                try:
                    rows, hit, miss = ray_tpu.get(
                        actor.take_blocks.remote(shard, routing, 4),
                        timeout=120)
                finally:
                    ray_tpu.kill(actor)
                assert rows == 4 * 2000 and hit + miss == 4
                hits += hit
            served = sum(r._chunk_bytes_served
                         for r in cluster.raylets) - served0
            return served, hits

        bytes_on, hits_on = run_arm(routing=True)
        bytes_off, hits_off = run_arm(routing=False)
        assert hits_on == 8
        assert hits_off == 0          # routing off advertises no node
        assert bytes_on < bytes_off, (bytes_on, bytes_off)
        for r in cluster.raylets:
            assert r.store.stats()["num_unsealed"] == 0
    finally:
        cluster.shutdown()
        GLOBAL_CONFIG._overrides.clear()
        GLOBAL_CONFIG._overrides.update(saved)


# --------------------------------------------------------------------------- #
# Same-host sealed-segment attach
# --------------------------------------------------------------------------- #

_CHUNK = 128 * 1024


@pytest.fixture()
def attach_cluster():
    """3 raylets on one host, tiny chunks; raylets driven directly."""
    from ray_tpu.cluster_utils import Cluster

    ray_tpu.shutdown()
    saved = dict(GLOBAL_CONFIG._overrides)
    GLOBAL_CONFIG._overrides.update({
        "object_transfer_chunk_bytes": _CHUNK,
        "rpc_connect_timeout_s": 1.0,
    })
    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 1})
    for _ in range(2):
        cluster.add_node(num_cpus=1)
    cluster.wait_for_nodes()
    try:
        yield cluster
    finally:
        cluster.shutdown()
        GLOBAL_CONFIG._overrides.clear()
        GLOBAL_CONFIG._overrides.update(saved)


def _seed_object(raylet, n_chunks, seed=0):
    from ray_tpu.core.ids import ObjectID

    oid = ObjectID.from_random()
    payload = np.random.default_rng(seed).integers(
        0, 255, size=n_chunks * _CHUNK, dtype=np.uint8).tobytes()
    raylet.store.put_serialized(oid, [payload])
    raylet.gcs.call("object_location_add",
                    {"object_id": oid, "node_id": raylet.node_id,
                     "size": raylet.store.local_size(oid)}, timeout=10)
    return oid


def _pull(raylet, oid):
    entry = raylet.gcs.call("object_locations_get", {"object_id": oid},
                            timeout=10)
    return raylet._pull_object_pipelined(oid, entry)


def test_same_host_attach_skips_the_socket(attach_cluster):
    """A same-host pull attaches the holder's sealed segment: identical
    bytes, zero chunk RPCs served, no unsealed buffers, counters in the
    raylet debug state."""
    holder, puller = attach_cluster.raylets[:2]
    oid = _seed_object(holder, n_chunks=8)
    assert _pull(puller, oid)
    assert puller.store.get_bytes(oid) == holder.store.get_bytes(oid)
    assert puller._attach_hits == 1
    assert puller._attach_bytes == 8 * _CHUNK
    assert holder._chunk_bytes_served == 0  # zero socket copies
    for r in attach_cluster.raylets:
        assert r.store.stats()["num_unsealed"] == 0
    dbg = puller.handle_debug_state({})["transfer"]
    assert dbg["attach_hits"] == 1
    assert dbg["attach_bytes"] == 8 * _CHUNK


def test_attach_registers_location_for_later_pullers(attach_cluster):
    holder, second, third = attach_cluster.raylets[:3]
    oid = _seed_object(holder, n_chunks=4, seed=1)
    assert _pull(second, oid)
    entry = holder.gcs.call("object_locations_get", {"object_id": oid},
                            timeout=10)
    hexes = {n.hex() if hasattr(n, "hex") else str(n)
             for n in entry["nodes"]}
    assert second.node_id.hex() in hexes  # attach announced the copy
    assert _pull(third, oid)
    assert third.store.get_bytes(oid) == holder.store.get_bytes(oid)


def test_attach_declines_when_knob_off(attach_cluster):
    holder, puller = attach_cluster.raylets[:2]
    GLOBAL_CONFIG._overrides["object_transfer_same_host_attach"] = False
    oid = _seed_object(holder, n_chunks=4, seed=2)
    assert _pull(puller, oid)
    assert puller._attach_hits == 0
    assert holder._chunk_bytes_served == 4 * _CHUNK  # the chunk path ran
    assert puller.store.get_bytes(oid) == holder.store.get_bytes(oid)


def test_attach_declines_when_link_model_armed(attach_cluster):
    """Bench honesty: a holder modeling a network link (serve delay or
    bandwidth cap) or a puller modeling RTT must keep measuring the
    network — attach silently bypassing the model would fake the A/B."""
    holder, puller, other = attach_cluster.raylets[:3]
    holder._chunk_serve_bw_bps = 1e9
    try:
        oid = _seed_object(holder, n_chunks=2, seed=3)
        assert _pull(puller, oid)
        assert puller._attach_hits == 0
    finally:
        holder._chunk_serve_bw_bps = 0.0
    puller._chunk_fetch_delay_s = 0.001
    try:
        oid2 = _seed_object(holder, n_chunks=2, seed=4)
        assert _pull(puller, oid2)
        assert puller._attach_hits == 0
    finally:
        puller._chunk_fetch_delay_s = 0.0
    # With no model armed the same topology attaches.
    oid3 = _seed_object(holder, n_chunks=2, seed=5)
    assert _pull(other, oid3)
    assert other._attach_hits == 1


# --------------------------------------------------------------------------- #
# Chaos: query exchange survives a node kill
# --------------------------------------------------------------------------- #


@pytest.mark.slow  # multi-node cluster + recovery: >10s under load
def test_sort_survives_node_kill_mid_exchange():
    """Kill the busiest worker node mid-sort (blocks past the 100 KiB
    inline threshold, so real store state dies with it). The epoch must
    complete with a correctly sorted output, recomputed work bounded by
    the victim's resident blocks + n_parts, and zero hangs."""
    from ray_tpu.chaos import HangWatchdog
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.data.streaming.lineage import core_reconstructions

    ray_tpu.shutdown()
    # CPU-less head: every task — and so every sorted partition — runs
    # and lives on a worker. The head (driver) survives the kill, but
    # the state it still needs does not.
    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 0})
    for _ in range(2):
        cluster.add_node(num_cpus=2)
    cluster.wait_for_nodes()
    cluster.connect()
    try:
        n_parts = 8

        def keyed(batch):
            return {"k": (batch["data"][:, 0].astype(np.int64)) % 50,
                    "data": batch["data"]}

        # Sized so even the per-bucket scatter blocks (~1/8 of a parent
        # block) clear the 100 KiB inline threshold: every intermediate
        # is REAL store state on some node (inline blocks live in the
        # GCS and would shrug off any kill), reduce placement routes to
        # the bucket holders, and the sorted partitions land spread
        # across the workers — so killing the most-loaded worker
        # necessarily destroys output the consumer hasn't pulled yet.
        ds = rd.range_tensor(32000, shape=(40,), parallelism=n_parts) \
            .map_batches(keyed).sort(key="k")
        base = core_reconstructions()
        rows = 0
        last_key = None
        killed = {}
        with HangWatchdog(limit_s=90.0) as wd:
            for i, batch in enumerate(ds.iter_batches(batch_size=2000)):
                rows += len(batch["k"])
                ks = np.asarray(batch["k"])
                assert (np.diff(ks) >= 0).all()  # sorted inside batches
                if last_key is not None:
                    assert ks[0] >= last_key  # ...and across them
                last_key = int(ks[-1])
                if i == 1 and not killed:
                    victim = max(
                        (r for r in cluster.raylets if not r.is_head),
                        key=lambda r: r.store.stats()["num_objects"])
                    killed["resident"] = \
                        victim.store.stats()["num_objects"]
                    cluster.crash_node(victim)
        wd.assert_no_hangs()
        assert rows == 32000
        recomputed = (core_reconstructions() - base) \
            + (ds._lineage.recomputed_blocks if ds._lineage else 0)
        assert recomputed >= 1, "the kill destroyed nothing the sort used"
        bound = max(killed.get("resident", 0), 1) + n_parts
        assert recomputed <= bound, (recomputed, killed)
        for raylet in cluster.raylets:
            assert raylet.store.stats()["num_unsealed"] == 0
    finally:
        try:
            cluster.shutdown()
        except Exception:  # noqa: BLE001 — nodes already churned
            pass
