"""Paged attention (`ray_tpu/ops/paged_attention.py`): the Pallas kernel,
run in the interpreter on CPU (RAY_TPU_PALLAS_INTERPRET=1), against the
dense reference it replaced; the dispatch rule's records; and the engine
end to end on a configuration the kernel takes.

The kernel must read a row's pages only below its live length: every arena
slot that is not live for its row holds NaN in the kernel's arena and zero
in the reference's, so a read past the length shows as a NaN."""

import numpy as np
import pytest

from conftest import assert_compiles_once

HD, BS = 128, 16


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")


def _case(s, kvh, groups, lens, max_blocks, dtype, seed=0):
    """Arenas with shuffled physical blocks and trash-padded table tails
    (as `test_paged_decode_matches_dense` builds them). `lens[i]` is row
    i's live length AFTER this call (0: an idle row, write_mask all
    false); its last min(s, len) positions are this call's queries."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    b = len(lens)
    nb = 1 + b * max_blocks
    perm = rng.permutation(np.arange(1, nb))
    bt = np.zeros((b, max_blocks), np.int32)
    shape = (nb, BS, kvh, HD)
    k, v = (rng.standard_normal(shape).astype(np.float32) for _ in "kv")
    live = np.zeros(shape[:2], bool)
    pos = np.zeros((b, s), np.int32)
    wmask = np.zeros((b, s), bool)
    off = 0
    for i, n in enumerate(lens):
        if not n:
            continue
        blocks = perm[off:off + -(-n // BS)]
        off += len(blocks)
        bt[i, :len(blocks)] = blocks
        p = np.arange(n)
        live[blocks[p // BS], p % BS] = True
        q_n = min(s, n)
        pos[i] = n - q_n + np.arange(s)
        wmask[i, :q_n] = True
    q = rng.standard_normal((b, s, kvh * groups, HD)).astype(np.float32)
    mask = live[:, :, None, None]
    arenas = {name: jnp.asarray(np.where(mask, a, fill), dtype)
              for name, a, fill in (("k_nan", k, np.nan), ("v_nan", v, np.nan),
                                    ("k", k, 0.0), ("v", v, 0.0))}
    return (jnp.asarray(q, dtype), arenas, jnp.asarray(bt), jnp.asarray(pos),
            jnp.asarray(wmask))


# Ragged lengths in one batch: 1, one ending exactly on a block edge, one
# in the middle of a block, one filling the whole table, an idle row.
MAX_BLOCKS = 34           # 544 positions: more than one KV chunk at any s
LENS = (1, 2 * BS, 5 * BS + 3, MAX_BLOCKS * BS, 0, 37)


@pytest.mark.parametrize("groups", [1, 4, 5])
@pytest.mark.parametrize("s", [1, 5, 32])
def test_kernel_matches_reference(interpret, s, groups):
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.paged_attention import (paged_attention,
                                             paged_attention_reference)

    # 2 KV heads, and Falcon-H1's 5 query heads a KV head over its 4
    q, arenas, bt, pos, wmask = _case(s, 4 if groups == 5 else 2, groups,
                                      LENS, MAX_BLOCKS, jnp.bfloat16,
                                      seed=s * 10 + groups)
    out = jax.jit(paged_attention)(q, arenas["k_nan"], arenas["v_nan"], bt,
                                   pos, wmask)
    ref = paged_attention_reference(q, arenas["k"], arenas["v"], bt, pos)
    out, ref = (np.asarray(a, np.float32) for a in (out, ref))
    assert out.shape == q.shape and out.dtype == np.float32
    # Nothing past a row's length was read, on any row, used or not.
    assert np.isfinite(out).all()
    used = np.asarray(wmask)
    # bf16 outputs of two orders of summation: a unit in the last place.
    np.testing.assert_allclose(out[used], ref[used], atol=2e-2, rtol=2e-2)
    assert np.abs(out[used] - ref[used]).mean() < 1e-3
    # An idle row writes zeros, never 0/0.
    assert (out[LENS.index(0)] == 0).all()


@pytest.mark.parametrize("s", [1, 32])
def test_sixteen_kv_heads_with_one_query_row_each(interpret, s):
    """The looped model's shape (`models/ouro.py`): plain multi-head
    attention, `groups` = 1 at 16 KV heads, so every product of the kernel
    is ONE query row a token against a head's chunk, the 16 heads taken
    out of a page by 8 paired strided loads. And its tables: a pass's
    pages lie a whole number of `num_blocks` up one arena, which to the
    kernel is a table like any other."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.paged_attention import (paged_attention,
                                             paged_attention_reference)

    lens, max_blocks = (1, 2 * BS, 5 * BS + 3, 0, 37), 6
    q, arenas, bt, pos, wmask = _case(s, 16, 1, lens, max_blocks,
                                      jnp.bfloat16, seed=160 + s)
    # the arena of a model with two passes: pass 1's pages are these, one
    # `num_blocks` up; pass 0's range holds NaN throughout
    nb = arenas["k"].shape[0]
    up = {name: jnp.concatenate([jnp.full_like(a, jnp.nan), a])
          for name, a in arenas.items()}
    out = jax.jit(paged_attention)(q, up["k_nan"], up["v_nan"], bt + nb,
                                   pos, wmask)
    ref = paged_attention_reference(q, arenas["k"], arenas["v"], bt, pos)
    out, ref = (np.asarray(a, np.float32) for a in (out, ref))
    assert np.isfinite(out).all()
    used = np.asarray(wmask)
    np.testing.assert_allclose(out[used], ref[used], atol=2e-2, rtol=2e-2)
    assert np.abs(out[used] - ref[used]).mean() < 1e-3
    assert (out[lens.index(0)] == 0).all()
    records = [r for r in _paged_records() if r["shape"][2] == 16]
    assert records and all(r["path"] == "pallas" for r in records)


def _chunk_and_sub():
    from ray_tpu.ops import paged_attention as pa

    return pa._CHUNK_TOKENS_FEW_ROWS, pa._SUB_TOKENS


def _row_patterns():
    """name -> the rows' live lengths: what the few-rows tile's chain of
    copies (a row's first chunk started under the row before) and its
    sub-blocks under a dynamic bound can get wrong."""
    chunk, sub = _chunk_and_sub()
    return {
        "live_idle_live": (40, 0, 300),
        "idle_first": (0, 0, 77, 200),
        "idle_last": (130, 19, 0),
        "all_idle": (0, 0, 0),
        "one_chunk": (chunk, 5, chunk),
        "chunk_plus_one": (chunk + 1, 5, chunk + 1),
        "one_sub_block": (sub, sub, 3, sub + 1),
        "one_token": (1, 1, 1),
        "two_chunks_then_one": (chunk + 90, 20, 2 * chunk, 100),
        "one_then_two_chunks": (100, 2 * chunk, 0, 20, chunk + 90),
    }


@pytest.mark.parametrize("kvh,groups,s", [
    (16, 1, 1), (2, 4, 1), (4, 5, 1), (4, 1, 5), (2, 4, 5)])
@pytest.mark.parametrize("pattern", [
    "live_idle_live", "idle_first", "idle_last", "all_idle", "one_chunk",
    "chunk_plus_one", "one_sub_block", "one_token", "two_chunks_then_one",
    "one_then_two_chunks"])
def test_few_rows_tile_over_row_patterns(interpret, pattern, kvh, groups, s):
    """One to twenty query rows a KV head (the looped model's 1, Mistral's
    4, Falcon-H1's 5, a speculative round's 5 and 20) over rows that are
    live, idle, one token, one sub-block, one chunk, a chunk and a token,
    two chunks: every page the table does not map and the dead tail of
    every last live page hold NaN, so a stale buffer row, a copy that
    landed in the wrong slot or a sub-block past the live length shows."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.paged_attention import (paged_attention,
                                             paged_attention_reference)

    lens = _row_patterns()[pattern]
    chunk, _ = _chunk_and_sub()
    q, arenas, bt, pos, wmask = _case(
        s, kvh, groups, lens, 2 * chunk // BS + 2, jnp.bfloat16,
        seed=len(pattern) * 100 + kvh * groups + s)
    out = jax.jit(paged_attention)(q, arenas["k_nan"], arenas["v_nan"], bt,
                                   pos, wmask)
    ref = paged_attention_reference(q, arenas["k"], arenas["v"], bt, pos)
    out, ref = (np.asarray(a, np.float32) for a in (out, ref))
    assert np.isfinite(out).all()
    used = np.asarray(wmask)
    np.testing.assert_allclose(out[used], ref[used], atol=2e-2, rtol=2e-2)
    if used.any():
        assert np.abs(out[used] - ref[used]).mean() < 1e-3
    for i, n in enumerate(lens):
        if not n:
            assert (out[i] == 0).all()
    (rec,) = [r for r in _paged_records() if r["shape"] == list(q.shape)]
    assert rec["path"] == "pallas" and rec["tile"].startswith("few rows")


def test_walk_chains_the_rows_that_walk():
    import jax.numpy as jnp

    from ray_tpu.ops.paged_attention import _walk

    walk = np.asarray(_walk(jnp.asarray([0, 5, 0, 600, 513, 0], jnp.int32),
                            512))
    assert walk.tolist() == [[0, 5, 0, 600, 513, 0],      # live length
                             [0, 0, 1, 1, 3, 5],          # chunks before
                             [1, 3, 3, 4, -1, -1]]        # next that walks
    assert np.asarray(_walk(jnp.zeros((3,), jnp.int32), 512))[2].tolist() \
        == [-1, -1, -1]


@pytest.mark.parametrize("s,kvh,groups", [(3, 2, 2), (1, 4, 1), (1, 4, 5)])
def test_kernel_float32_arena_is_near_exact(interpret, s, kvh, groups):
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.paged_attention import (paged_attention,
                                             paged_attention_reference)

    q, arenas, bt, pos, wmask = _case(s, kvh, groups, LENS, MAX_BLOCKS,
                                      jnp.float32)
    out = jax.jit(paged_attention)(q, arenas["k_nan"], arenas["v_nan"], bt,
                                   pos, wmask)
    ref = paged_attention_reference(q, arenas["k"], arenas["v"], bt, pos)
    used = np.asarray(wmask)
    np.testing.assert_allclose(np.asarray(out)[used], np.asarray(ref)[used],
                               atol=1e-5, rtol=1e-5)


def test_without_write_mask_every_query_is_used(interpret):
    import jax.numpy as jnp

    from ray_tpu.ops.paged_attention import (paged_attention,
                                             paged_attention_reference)

    q, arenas, bt, pos, _ = _case(1, 2, 4, (40, 7), 4, jnp.bfloat16)
    out = paged_attention(q, arenas["k_nan"], arenas["v_nan"], bt, pos)
    ref = paged_attention_reference(q, arenas["k"], arenas["v"], bt, pos)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=2e-2)


def test_timing_script_rehearses(interpret, tmp_path, capsys):
    """`scripts/time_paged_kernels.py --rehearsal`: the cells' decode
    shapes cut small, through the interpreter, against the reference; no
    device number comes out of a CPU."""
    import importlib.util
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "time_paged_kernels",
        os.path.join(root, "scripts", "time_paged_kernels.py"))
    script = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(script)
    assert script.main(["--rehearsal", "--out", str(tmp_path)]) == 0
    (line,) = [json.loads(x) for x in capsys.readouterr().out.splitlines()
               if x.startswith("{")]
    with open(tmp_path / "paged_kernels.json") as f:
        assert json.load(f) == [line]
    assert line["tree"] == "this" and line["platform"] == "cpu"
    assert set(line["us"]) == set(script.REHEARSAL)
    assert all(v is None for key in ("us", "us_op", "roofline_pct")
               for v in line[key].values())
    assert max(line["max_abs_diff"].values()) <= script.TOLERANCE
    assert {(p[0], p[1]) for p in line["paths"]} == {
        ("paged_decode", "pallas"), ("paged_prefill", "pallas")}


# --------------------------------------------------------------------------- #
# The dispatch rule and its records
# --------------------------------------------------------------------------- #


def _paged_records():
    from ray_tpu.ops.attention import pallas_status

    return [r for r in pallas_status() if r["pass"].startswith("paged_")]


def _shapes(s=1, hd=HD, bs=BS, kvh=2, dtype="bfloat16"):
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)
    return (jnp.zeros((2, s, 2 * kvh, hd), dt),
            jnp.zeros((9, bs, kvh, hd), dt), jnp.zeros((9, bs, kvh, hd), dt),
            jnp.zeros((2, 4), jnp.int32), jnp.zeros((2, s), jnp.int32))


@pytest.mark.parametrize("switch,kwargs,want_pass,want_path,want_reason", [
    (False, {}, "paged_decode", "reference", "platform cpu"),
    (False, {"s": 3}, "paged_prefill", "reference", "platform cpu"),
    (True, {}, "paged_decode", "pallas", ""),
    (True, {"s": 3}, "paged_prefill", "pallas", ""),
    (True, {"hd": 64}, "paged_decode", "reference",
     "head_dim not a multiple of 128"),
    (True, {"bs": 8}, "paged_decode", "reference",
     "block_size not a multiple of the dtype's sublane tile"),
    (True, {"kvh": 3}, "paged_decode", "reference",
     "kv_heads do not fill the arena's tiles"),
    (True, {"dtype": "float16"}, "paged_decode", "reference",
     "q and arena not both bfloat16 or both float32"),
    # the tile is the shape's: 2 query heads a KV head x 64 tokens are the
    # last few-rows tile, one token more the first many-rows tile
    (True, {"s": 64}, "paged_prefill", "pallas", ""),
    (True, {"s": 65}, "paged_prefill", "pallas", ""),
    (True, {"dtype": "float32"}, "paged_decode", "pallas", ""),
])
def test_dispatch_records(monkeypatch, switch, kwargs, want_pass, want_path,
                          want_reason):
    from ray_tpu.ops.attention import reset_pallas_status
    from ray_tpu.ops.paged_attention import paged_attention, paged_calls

    if switch:
        monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("RAY_TPU_PALLAS_INTERPRET", raising=False)
    reset_pallas_status()
    args = _shapes(**kwargs)
    out = paged_attention(*args)
    assert out.shape == args[0].shape
    (rec,) = _paged_records()
    assert (rec["pass"], rec["path"], rec["reason"]) == (
        want_pass, want_path, want_reason)
    assert rec["shape"] == list(args[0].shape) and rec["calls"] == 1
    assert rec["dtype"] == kwargs.get("dtype", "bfloat16")
    label = want_path + (f": {want_reason}" if want_reason else "")
    assert paged_calls() == {(want_pass, label): 1}
    # Which tile of the kernel the call's shape was given: said by the
    # record, and nothing for a call the kernel did not take.
    want_tile = "" if want_path == "reference" else \
        "many rows" if kwargs.get("s", 1) * 2 > 128 else "few rows"
    assert rec["tile"].startswith(want_tile) and bool(rec["tile"]) == bool(
        want_tile)
    assert paged_calls("tile") == {(want_pass, rec["tile"]): 1}


def test_interpret_switch_is_refused_on_tpu(interpret, monkeypatch):
    from ray_tpu.ops import attention
    from ray_tpu.ops.paged_attention import paged_attention

    monkeypatch.setattr(attention, "_platform", lambda: "tpu")
    with pytest.raises(RuntimeError, match="CPU test switch"):
        paged_attention(*_shapes())


# --------------------------------------------------------------------------- #
# The engine, end to end, on a configuration the kernel takes
# --------------------------------------------------------------------------- #


@pytest.fixture(scope="module")
def wide_llama():
    """Two layers, 4 heads over 2 KV heads of 128: the smallest model the
    kernel takes (`LlamaConfig.tiny` has head_dim 32, `small` 64). In
    float32, so that the two paths' orders of summation cannot flip a
    greedy argmax."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import Llama, LlamaConfig

    cfg = LlamaConfig(vocab_size=256, n_positions=256, n_embd=512, n_layer=2,
                      n_head=4, n_kv_head=2, intermediate=256,
                      use_flash=False, dtype=jnp.float32)
    model = Llama(cfg)
    params = jax.jit(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))()
    return model, params


def _drive(model, params, mesh=None, **overrides):
    """The same seeded requests through one engine: mixed admission over
    3 slots, a prompt of three prefill chunks, an arena too small for
    everyone (preemption), and a repeated prompt (prefix-cache hit)."""
    from ray_tpu.inference import EngineConfig, InferenceEngine

    kwargs = dict(batch_slots=3, block_size=BS, num_blocks=12,
                  max_blocks_per_seq=8, prefill_chunk=32)
    kwargs.update(overrides)
    engine = InferenceEngine(EngineConfig(**kwargs), model=model,
                             params=params, mesh=mesh)
    rng = np.random.default_rng(7)
    prompts = [list(map(int, rng.integers(1, 250, n)))
               for n in (5, 70, 17, 33, 40)]
    reqs = [engine.add_request(p, max_new_tokens=20 + 3 * i)
            for i, p in enumerate(prompts)]
    engine.run_until_idle()
    # Alone in the arena the prompt's blocks stay cached, so its second
    # run is a hit whatever the crowd above evicted.
    for _ in range(2):
        reqs.append(engine.add_request(prompts[1], max_new_tokens=6))
        engine.run_until_idle()
    assert all(r.state == "FINISHED" for r in reqs)
    engine.check_no_leaks()
    return engine, reqs


def test_engine_tokens_identical_kernel_and_reference(wide_llama,
                                                      monkeypatch):
    model, params = wide_llama
    monkeypatch.delenv("RAY_TPU_PALLAS_INTERPRET", raising=False)
    ref_engine, ref = _drive(model, params)
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    engine, got = _drive(model, params)
    for a, b in zip(got, ref):
        assert a.generated == b.generated, a.request_id
    stats, ref_stats = engine.stats(), ref_engine.stats()
    # The traffic did what it was built for, on both engines alike.
    assert stats["preemptions"] == ref_stats["preemptions"] >= 1
    assert stats["prefix_cache"]["hits"] >= 1
    assert got[-1].cached_tokens > 0
    steps = stats["steps"]                               # chunked prefill
    assert steps["prefill"] + steps["chunks_aboard"] > len(got)
    assert steps["chunks_aboard"] > 0
    assert_compiles_once(stats, "prefill_compiles", "decode_compiles")
    assert stats["paged_attn"] == {"decode": "pallas", "prefill": "pallas"}
    assert all(tile.startswith("few rows")
               for tile in stats["paged_attn_tile"].values())
    assert ref_stats["paged_attn_tile"] == {"decode": "", "prefill": ""}
    assert ref_stats["paged_attn"] == {
        "decode": "reference: platform cpu",
        "prefill": "reference: platform cpu"}
    engine.drop_prefix_cache()
    engine.check_no_leaks()
    assert engine.stats()["kv"]["blocks_in_use"] == 0


def test_engine_spec_decode_through_the_kernel(wide_llama, monkeypatch):
    """The draft's prefill, `propose_fn` (the op inside `lax.scan`) and
    `verify_fn` [B, k+1] all read through the kernel; greedy verify keeps
    the output that of the plain engine."""
    model, params = wide_llama
    monkeypatch.delenv("RAY_TPU_PALLAS_INTERPRET", raising=False)
    _, ref = _drive(model, params, num_blocks=40)
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    engine, got = _drive(model, params, num_blocks=40,
                         spec_decode_draft_len=3)
    for a, b in zip(got, ref):
        assert a.generated == b.generated, a.request_id
    sd = engine.stats()["spec_decode"]
    assert sd["rounds"] > 0
    assert_compiles_once(sd, "draft_prefill_compiles", "propose_compiles",
                         "verify_compiles")


def test_engine_tp2_runs_the_kernel_inside_shard_map(wide_llama, monkeypatch):
    """With a tp mesh the arena is sharded on its kv-head axis: each device
    runs the kernel on its own heads (the records show the LOCAL shape),
    and the tokens are those of the one-device engine."""
    import jax

    from ray_tpu.ops.attention import reset_pallas_status
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    model, params = wide_llama
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    _, ref = _drive(model, params, num_blocks=40)
    reset_pallas_status()
    mesh = build_mesh(MeshSpec({"tp": 2}), devices=jax.devices()[:2])
    engine, got = _drive(model, params, mesh=mesh, num_blocks=40)
    for a, b in zip(got, ref):
        assert a.generated == b.generated, a.request_id
    assert engine.stats()["paged_attn"]["decode"] == "pallas"
    local = [3, 1, model.config.n_head // 2, HD]
    assert [r["shape"] for r in _paged_records()
            if r["pass"] == "paged_decode"] == [local]
