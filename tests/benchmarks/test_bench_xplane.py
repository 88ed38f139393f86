"""The trace reduction: interval arithmetic, kernel lookup by name,
exposed-collective arithmetic, idle gaps; on hand-made events and on a
small trace recorded on the chip."""

import json
import os

import pytest

import _paths  # noqa: F401
from benchmarks import xplane

MS = 1_000_000


def ev(name, start_ms, dur_ms, scope=""):
    return [name, int(start_ms * MS), int(dur_ms * MS), scope]


def synthetic():
    ops = [
        ev("marker", 0, 1),
        ev("fusion.1", 10, 20, "bf16[8,1024,4096] fusion"),
        ev("flash_fwd.3", 30, 10, "bf16[128,1024,64] custom-call"),
        ev("flash_bwd_dq.7", 40, 5, "bf16[128,1024,64] custom-call"),
        ev("flash_bwd_dkv.8", 45, 5, "bf16[128,1024,64] custom-call"),
        ev("all-reduce.1", 50, 10),          # 50-60, overlapped 50-55
        ev("fusion.2", 50, 5),
        ev("all-reduce-done.2", 70, 4),      # fully exposed
        ev("marker", 99, 1),
    ]
    modules = [ev("jit_step_with_rules(123)", 10, 50),
               ev("jit_step_with_rules(123)", 60, 14)]
    host = [ev("bench.batch_fetch", 60, 9), ev("bench.wait_device", 74, 30)]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": modules}]},
        {"name": "/host:CPU", "lines": [{"name": "t", "events": host}]}]}


@pytest.mark.parametrize("intervals,want", [
    ([(0, 10), (5, 15), (20, 30)], [(0, 15), (20, 30)]),
    ([(5, 6), (0, 10)], [(0, 10)]),
    ([(3, 3)], []),
    ([(0, 1), (1, 2)], [(0, 2)]),
])
def test_union(intervals, want):
    assert xplane.union(intervals) == want


@pytest.mark.parametrize("a,b,want", [
    ([(0, 10)], [(2, 4), (6, 12)], [(0, 2), (4, 6)]),
    ([(0, 10), (20, 30)], [(5, 25)], [(0, 5), (25, 30)]),
    ([(0, 10)], [], [(0, 10)]),
    ([(0, 10)], [(0, 10)], []),
    ([(0, 4), (6, 10)], [(3, 7)], [(0, 3), (7, 10)]),
])
def test_subtract(a, b, want):
    assert xplane.subtract(a, b) == want


def test_gaps_are_the_complement_of_busy():
    busy = xplane.union([(10, 20), (40, 50)])
    assert xplane.gaps(busy, (0, 60)) == [(0, 10), (20, 40), (50, 60)]


@pytest.mark.parametrize("name,want", [
    ("all-reduce.1", True), ("all-reduce-start.12", True),
    ("all-gather-done", True), ("%reduce-scatter.3", True),
    ("collective-permute-start.1", True), ("fusion.3", False),
    ("reduce.4", False), ("all-reduce-fusion", False),
])
def test_collectives_are_found_by_hlo_name(name, want):
    assert xplane.is_collective(name) is want


def test_digest_busy_idle_union():
    d = xplane.digest(synthetic())
    assert d["n_devices"] == 1
    assert d["window_s"] == pytest.approx(0.100)
    # busy: 0-1, 10-60, 70-74, 99-100
    assert d["busy_s"] == pytest.approx(0.056)
    assert d["longest_idle_gap_s"] == pytest.approx(0.025)


def test_digest_exposed_collective_arithmetic():
    d = xplane.digest(synthetic())
    assert d["collective_s"] == pytest.approx(0.014)
    # all-reduce.1 is covered by fusion.2 for 5 of its 10 ms
    assert d["exposed_collective_s"] == pytest.approx(0.009)


def test_digest_modules_and_kernels_by_name():
    from benchmarks.layer_metrics._common import kernel_label

    d = xplane.digest(synthetic())
    assert xplane.module_matching(d, r"^jit_step_with_rules$") == \
        (2, pytest.approx(0.064))
    assert xplane.ops_matching(d, kernel_label("flash_fwd")) == \
        (1, pytest.approx(0.010))
    # dq does not swallow dkv
    assert xplane.ops_matching(d, kernel_label("flash_bwd_dq")) == \
        (1, pytest.approx(0.005))
    assert xplane.ops_matching(d, kernel_label("flash_bwd_dkv")) == \
        (1, pytest.approx(0.005))


def test_digest_names_idle_gaps_by_the_host_span():
    d = xplane.digest(synthetic())
    gaps = dict(d["breakdown"]["idle_gaps"])
    # 60-70 is covered by bench.batch_fetch (9 of 10 ms), 74-99 by
    # bench.wait_device; nothing names 1-10
    assert gaps["bench.batch_fetch"] == pytest.approx(0.010)
    assert gaps["bench.wait_device"] == pytest.approx(0.025)
    assert gaps["unattributed"] == pytest.approx(0.009)
    assert len(d["breakdown"]["device_ops"]) <= 10


@pytest.mark.parametrize("text,want", [
    ("%fusion.11 = (f32[50304,1024]{1,0:T(8,128)}, f32[50304,1024]{1,0}) "
     "fusion(f32[50304,1024]{1,0} %p.1, f32[]{:T(128)S(6)} %sub.551), "
     "kind=kOutput, calls=%fused_computation.15",
     ("fusion.11", "f32[50304,1024] fusion")),
    ("%flash_bwd_dkv.47 = (bf16[128,1024,64]{2,1,0:T(8,128)(2,1)}, "
     "bf16[128,1024,64]{2,1,0}) custom-call(bf16[128,1024,64]{2,1,0} %x), "
     "custom_call_target=\"tpu_custom_call\"",
     ("flash_bwd_dkv.47", "bf16[128,1024,64] custom-call")),
    ("%broadcast.940 = f32[16,4096,8,4,128]{4,3,2,1,0:T(4,128)} "
     "broadcast(f32[16,4096,8,128]{3,2,1,0:T(8,128)} %bitcast.210), "
     "dimensions={0,1,2,4}",
     ("broadcast.940", "f32[16,4096,8,4,128] broadcast")),
    ("%all-reduce-start.3 = f32[1024]{0} all-reduce-start(f32[1024]{0} %g)",
     ("all-reduce-start.3", "f32[1024] all-reduce-start")),
    ("jit_step_with_rules(123)", ("jit_step_with_rules(123)", "")),
])
def test_an_op_is_named_by_its_hlo_name_and_shape(text, want):
    assert xplane.split_hlo(text) == want


def test_breakdown_groups_ops_of_one_kind():
    ops = dict(xplane.digest(synthetic())["breakdown"]["device_ops"])
    assert ops["module:jit_step_with_rules"] == pytest.approx(0.064)
    assert ops["fusion bf16[8,1024,4096] fusion x1"] == pytest.approx(0.020)
    assert ops["flash_fwd bf16[128,1024,64] custom-call x1"] == \
        pytest.approx(0.010)


def test_digest_of_a_trace_without_a_device_is_none():
    trace = {"planes": [{"name": "/host:CPU", "lines": [
        {"name": "t", "events": [ev("bench.x", 0, 1)]}]}]}
    assert xplane.digest(trace) is None
    assert xplane.sample(trace, 10) == {"planes": []}


def test_digest_averages_over_chips():
    one = synthetic()
    two = {"planes": one["planes"] + [
        {**one["planes"][0], "name": "/device:TPU:1"}]}
    d1, d2 = xplane.digest(one), xplane.digest(two)
    assert d2["n_devices"] == 2
    assert d2["busy_s"] == pytest.approx(d1["busy_s"])
    assert d2["modules"] == d1["modules"]


def test_sample_cuts_a_trace_to_its_first_span():
    cut = xplane.sample(synthetic(), 35 * MS)
    names = [e[0] for e in cut["planes"][0]["lines"][0]["events"]]
    assert names == ["marker", "fusion.1", "flash_fwd.3"]


def test_load_reads_a_cpu_profile_and_finds_no_device(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.test_span"):
        jnp.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    path = xplane.find_xplane(str(tmp_path))
    assert path is not None
    loaded = xplane.load(path)
    spans = [e[0] for p in loaded["planes"] for line in p["lines"]
             for e in line["events"]]
    assert "bench.test_span" in spans
    assert xplane.digest(loaded) is None
    assert xplane.outline(path)["planes"]


RECORDED = os.path.join(_paths.DATA, "train_trace_sample.json")
RECORDED_DECODE = os.path.join(_paths.DATA, "decode_trace_sample.json")


def test_recorded_decode_trace_reduces_to_sane_numbers():
    """0.6 s of a real `serve_mistral7b_decode_heavy` trace (TPU v5e,
    PR 23): three whole decode steps of ~185 ms and the GQA broadcast on
    top."""
    with open(RECORDED_DECODE) as f:
        d = xplane.digest(json.load(f))
    runs, seconds = xplane.module_matching(d, r"^jit_decode_fn$")
    assert runs == 4 and 150 < seconds / runs * 1e3 < 200
    assert 0.9 < d["busy_s"] / d["window_s"] < 1.0
    top = [name for name, _ in d["breakdown"]["device_ops"]]
    assert top[0] == "module:jit_decode_fn"
    assert top[1].startswith("broadcast f32[16,4096,8,4,128]")


def test_recorded_chip_trace_reduces_to_sane_numbers():
    """A cut of a real `train_gpt2m_1chip` trace (TPU v5e, PR 23)."""
    from benchmarks.layer_metrics._common import FLASH_KERNELS, kernel_label

    with open(RECORDED) as f:
        d = xplane.digest(json.load(f))
    assert d["n_devices"] == 1
    assert 0 < d["busy_s"] <= d["window_s"]
    runs, step_s = xplane.module_matching(d, r"^jit_step_with_rules$")
    assert runs >= 1 and step_s > 0
    for kernel in FLASH_KERNELS:
        calls, seconds = xplane.ops_matching(d, kernel_label(kernel))
        assert calls >= 24 and seconds > 0, kernel
    assert d["exposed_collective_s"] == 0
