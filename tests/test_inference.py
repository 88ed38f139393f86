"""Continuous-batching inference engine: block manager, scheduler, Serve.

Parity target: Orca-style iteration-level scheduling + vLLM-style paged
KV cache. The engine must (a) match the dense KV-decode reference token
for token, (b) never recompile its two step programs, (c) degrade via
preemption instead of OOM, and (d) leak zero blocks across any schedule.
"""

import functools
import threading
import time

import pytest

from conftest import assert_compiles_once
from ray_tpu.inference.kv_cache import TRASH_BLOCK, BlockManager


# --------------------------------------------------------------------- #
# Block manager (pure bookkeeping, no jax)
# --------------------------------------------------------------------- #


def test_block_manager_alloc_free():
    bm = BlockManager(num_blocks=9, block_size=4)
    assert bm.capacity == 8 and bm.num_free() == 8
    bm.register("a")
    assert bm.ensure("a", 10)          # 3 blocks
    assert bm.blocks_in_use() == 3
    assert len(bm.block_table("a")) == 3
    assert TRASH_BLOCK not in bm.block_table("a")
    assert bm.ensure("a", 10)          # idempotent
    assert bm.blocks_in_use() == 3
    assert bm.free("a") == 3
    assert bm.blocks_in_use() == 0
    bm.check_consistency()


def test_block_manager_exhaustion_returns_false():
    bm = BlockManager(num_blocks=5, block_size=2)   # 4 allocatable
    bm.register("a")
    bm.register("b")
    assert bm.ensure("a", 6)           # 3 blocks
    assert not bm.ensure("b", 4)       # needs 2, only 1 free
    assert bm.ensure("b", 2)           # 1 block fits
    assert not bm.fits(100)
    bm.free("a")
    assert bm.ensure("b", 8)
    bm.free("b")
    bm.check_consistency()
    assert bm.blocks_in_use() == 0


def test_block_manager_fork_refcounts_and_cow():
    bm = BlockManager(num_blocks=17, block_size=4)
    bm.register("parent")
    assert bm.ensure("parent", 10)     # 3 blocks
    bm.fork("parent", "child")
    assert bm.block_table("child") == bm.block_table("parent")
    assert bm.blocks_in_use() == 3     # shared, not copied
    # Appending to a shared tail must copy-on-write.
    cow = bm.ensure_appendable("child")
    assert cow is not None and cow[1] != -1
    src, dst = cow
    assert bm.block_table("child")[-1] == dst
    assert bm.block_table("parent")[-1] == src
    assert bm.blocks_in_use() == 4
    assert bm.ensure_appendable("child") is None   # now exclusive
    # Freeing the parent keeps the shared prefix alive for the child.
    assert bm.free("parent") == 1      # only the old tail was exclusive
    assert bm.blocks_in_use() == 3
    assert bm.free("child") == 3
    assert bm.blocks_in_use() == 0
    bm.check_consistency()


def test_block_manager_cow_exhaustion_degrades():
    bm = BlockManager(num_blocks=4, block_size=2)   # 3 allocatable
    bm.register("p")
    assert bm.ensure("p", 6)           # all 3 blocks
    bm.fork("p", "c")
    assert bm.ensure_appendable("c") == (bm.block_table("c")[-1], -1)
    bm.free("p")
    bm.free("c")
    bm.check_consistency()


def test_block_manager_randomized_fuzz():
    """Seeded fork/append/free fuzz: any interleaving of COW forks,
    appends, frees and radix-style table adoptions keeps the refcount
    invariants (`check_consistency` after EVERY op) and a full drain
    returns the arena to empty — the zero-leak contract the engine's
    `check_no_leaks` builds on."""
    import random

    rng = random.Random(0x5EED)
    bm = BlockManager(num_blocks=25, block_size=4)
    tokens = {}                        # live seq_id -> token count
    spawned = 0
    for _ in range(600):
        roll = rng.random()
        if roll < 0.35 or not tokens:              # new sequence
            sid = f"s{spawned}"
            spawned += 1
            n = rng.randint(1, 12)
            bm.register(sid)
            if bm.ensure(sid, n):
                tokens[sid] = n
            else:                                  # pool full: back out
                bm.free(sid)
        elif roll < 0.60:                          # append one token
            sid = rng.choice(sorted(tokens))
            cow = bm.ensure_appendable(sid)
            if cow is not None and cow[1] == -1:
                pass                               # COW exhausted: no-op
            elif bm.ensure(sid, tokens[sid] + 1):
                tokens[sid] += 1
        elif roll < 0.75:                          # fork (shared prefix)
            child = f"s{spawned}"
            spawned += 1
            parent = rng.choice(sorted(tokens))
            bm.fork(parent, child)
            tokens[child] = tokens[parent]
        elif roll < 0.85:                          # adopt (radix-style)
            twin = f"s{spawned}"
            spawned += 1
            donor = rng.choice(sorted(tokens))
            bm.register_with_blocks(twin, bm.block_table(donor))
            tokens[twin] = tokens[donor]
        else:                                      # free
            sid = rng.choice(sorted(tokens))
            bm.free(sid)
            del tokens[sid]
        bm.check_consistency()
        assert bm.blocks_in_use() <= bm.capacity
    for sid in sorted(tokens):
        bm.free(sid)
        bm.check_consistency()
    assert bm.blocks_in_use() == 0 and bm.num_seqs() == 0


# --------------------------------------------------------------------- #
# Radix prefix cache (pure bookkeeping, no jax)
# --------------------------------------------------------------------- #


def test_radix_cache_insert_match_split_evict():
    from ray_tpu.inference.kv_cache import RadixPrefixCache

    bm = BlockManager(num_blocks=17, block_size=4)
    cache = RadixPrefixCache(bm)
    bm.register("donor")
    assert bm.ensure("donor", 12)
    table = list(bm.block_table("donor"))
    assert cache.insert(list(range(12)), table) == 3   # 3 novel blocks
    # The donor frees; the cache's synthetic table keeps the KV alive.
    assert bm.free("donor") == 0
    cache.check_consistency()
    assert cache.cached_blocks() == 3 == bm.blocks_in_use()

    # Full-prefix hit returns the donor's physical blocks in order.
    hit, node = cache.match(list(range(12)))
    assert hit == table and node is not None

    # Partial match splits the edge so the returned node covers EXACTLY
    # the matched span (pinning it protects nothing extra).
    hit2, node2 = cache.match(list(range(8)) + [77, 78, 79, 80])
    assert hit2 == table[:2]
    cache.check_consistency()
    cache.pin(node2)

    # Adoption: a reader increfs the cached blocks, frees its own ref.
    bm.register_with_blocks("reader", hit2)
    bm.check_consistency()
    assert bm.free("reader") == 0          # cache still holds them
    assert cache.cached_blocks() == 3

    # Eviction is LRU over UNPINNED leaves: the pinned 2-block prefix
    # survives unbounded pressure; only the unpinned tail leaf goes.
    assert cache.evict_for(1000) == 1
    assert cache.cached_blocks() == 2
    cache.unpin(node2)
    assert cache.evict_for(1000) == 2
    assert cache.cached_blocks() == 0
    cache.check_consistency()
    assert bm.blocks_in_use() == 0
    s = cache.stats()
    assert s["lookups"] == 2 and s["hits"] == 2
    assert s["inserted_blocks"] == 3 and s["evicted_blocks"] == 3


def test_radix_cache_dedupes_branches_and_clears():
    from ray_tpu.inference.kv_cache import RadixPrefixCache

    bm = BlockManager(num_blocks=17, block_size=4)
    cache = RadixPrefixCache(bm)
    bm.register("d1")
    assert bm.ensure("d1", 12)
    t1 = list(bm.block_table("d1"))
    cache.insert(list(range(12)), t1)
    bm.free("d1")

    # Second donor shares the first 8 tokens, diverges in block 3: the
    # shared span dedupes onto the tree's blocks (the donor's duplicates
    # return to the pool when it frees), only the novel block is kept.
    bm.register("d2")
    assert bm.ensure("d2", 12)
    t2 = list(bm.block_table("d2"))
    toks2 = list(range(8)) + [90, 91, 92, 93]
    assert cache.insert(toks2, t2) == 1
    assert bm.free("d2") == 2              # the two duplicated blocks
    cache.check_consistency()
    assert cache.cached_blocks() == 4 == bm.blocks_in_use()

    # Both branches resolve to their own tails over the shared prefix.
    hit1, _ = cache.match(list(range(12)))
    hit2, _ = cache.match(toks2)
    assert hit1 == t1
    assert hit2 == t1[:2] + t2[2:]
    # Partial blocks never match (alphabet is FULL blocks only).
    hit3, node3 = cache.match(list(range(3)))
    assert hit3 == [] and node3 is None

    assert cache.clear() == 4
    cache.check_consistency()
    assert cache.cached_blocks() == 0 and bm.blocks_in_use() == 0


# --------------------------------------------------------------------- #
# Engine
# --------------------------------------------------------------------- #


@pytest.fixture(scope="module")
def tiny_llama():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import Llama, LlamaConfig

    cfg = LlamaConfig.tiny(seq=256)
    model = Llama(cfg)
    params = jax.jit(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))()
    return model, params


@functools.lru_cache(maxsize=None)
def _full_forward(model):
    import jax

    return jax.jit(lambda p, ids, last: model.apply(p, ids)[0, last])


def _reference_generate(model, params, prompt, n):
    """Greedy loop over the FULL causal forward, which has no cache at
    all: the engine must match it exactly. One program for every length
    (the sequence sits in a row of zeros 256 wide; a causal model's logits
    at a position do not see what follows it)."""
    import jax.numpy as jnp

    forward = _full_forward(model)
    ids = list(prompt)
    while len(ids) < len(prompt) + n:
        row = jnp.zeros((1, 256), jnp.int32).at[0, :len(ids)].set(
            jnp.asarray(ids, jnp.int32))
        ids.append(int(jnp.argmax(forward(params, row, len(ids) - 1))))
    return ids[len(prompt):]


def _make_engine(tiny_llama, **overrides):
    from ray_tpu.inference import EngineConfig, InferenceEngine

    model, params = tiny_llama
    draft = {k: overrides.pop(k) for k in ("draft_model", "draft_params")
             if k in overrides}
    kwargs = dict(batch_slots=3, block_size=4, num_blocks=64,
                  max_blocks_per_seq=16, prefill_chunk=8)
    kwargs.update(overrides)
    return InferenceEngine(EngineConfig(**kwargs), model=model,
                           params=params, **draft)


def test_engine_matches_reference_and_compiles_once(tiny_llama):
    model, params = tiny_llama
    engine = _make_engine(tiny_llama)
    reqs = [engine.add_request([1 + i, 2 + i, 3 + i, 4 + i],
                               max_new_tokens=4 + i) for i in range(5)]
    engine.run_until_idle()
    for req in reqs:
        assert req.state == "FINISHED"
        ref = _reference_generate(model, params, req.prompt,
                                  req.max_new_tokens)
        assert req.generated == ref, req.request_id
    stats = engine.stats()
    # The whole run — mixed admissions, exits, chunked prefill — used
    # exactly one prefill program and one decode program.
    assert_compiles_once(stats, "prefill_compiles", "decode_compiles")
    engine.check_no_leaks()


def test_chunked_prefill_interleaves_with_decode(tiny_llama):
    """A long prompt prefilling in chunks must not stall an already-
    decoding sequence's token emission."""
    events = []
    engine = _make_engine(tiny_llama, batch_slots=2, prefill_chunk=4)
    short = engine.add_request(
        [1, 2, 3], max_new_tokens=12,
        on_token=lambda r, t: events.append(("short", t)),
        request_id="short")
    # Let the short request finish prefill and start decoding.
    while short.state != "DECODE":
        engine.step()
    long = engine.add_request(
        list(range(1, 33)), max_new_tokens=4,      # 8 prefill chunks
        on_token=lambda r, t: events.append(("long", t)),
        request_id="long")
    engine.run_until_idle()
    assert short.state == "FINISHED" and long.state == "FINISHED"
    first_long = next(i for i, (who, _) in enumerate(events)
                      if who == "long")
    short_before_long = sum(1 for who, _ in events[:first_long]
                            if who == "short")
    # Several short-request tokens were emitted while the long prompt was
    # still prefilling (with chunk=4 its prefill spans 8 engine steps).
    assert short_before_long >= 3, events
    engine.check_no_leaks()


def test_preemption_recovers_and_leaks_nothing(tiny_llama):
    model, params = tiny_llama
    # Arena so small that two growing sequences cannot both stay
    # resident: the later arrival must be preempted, recomputed, and
    # still finish with exactly its solo output.
    engine = _make_engine(tiny_llama, batch_slots=2, block_size=2,
                          num_blocks=9, max_blocks_per_seq=8,
                          prefill_chunk=4)
    a = engine.add_request([1, 2, 3], max_new_tokens=10, request_id="a")
    b = engine.add_request([4, 5, 6], max_new_tokens=10, request_id="b")
    engine.run_until_idle()
    assert a.state == b.state == "FINISHED"
    stats = engine.stats()
    assert stats["preemptions"] >= 1
    # Priority: the older request is never the victim.
    assert a.preemptions == 0 and b.preemptions >= 1
    assert a.generated == _reference_generate(model, params, a.prompt, 10)
    assert b.generated == _reference_generate(model, params, b.prompt, 10)
    # The victim's blocks were freed and re-acquired; nothing leaked —
    # the only remaining references are the radix cache's donations,
    # and dropping those drains the arena to empty.
    engine.check_no_leaks()
    engine.drop_prefix_cache()
    engine.check_no_leaks()
    assert engine.stats()["kv"]["blocks_in_use"] == 0
    assert_compiles_once(stats, "decode_compiles")  # preemption didn't recompile


def test_engine_rejects_oversized_request(tiny_llama):
    engine = _make_engine(tiny_llama, block_size=2, num_blocks=8,
                          max_blocks_per_seq=4)
    with pytest.raises(ValueError, match="token slots"):
        engine.add_request(list(range(20)), max_new_tokens=20)
    engine.check_no_leaks()


def test_engine_eager_smoke(tiny_llama):
    """Interpreter-mode (no jit) smoke: the tier-1 fast path through the
    whole scheduler without paying any XLA compile."""
    engine = _make_engine(tiny_llama, use_jit=False, batch_slots=2,
                          prefill_chunk=4)
    req = engine.add_request([1, 2, 3], max_new_tokens=3)
    engine.run_until_idle()
    assert req.state == "FINISHED" and len(req.generated) == 3
    engine.check_no_leaks()


def test_engine_loop_threaded_streaming(tiny_llama):
    from ray_tpu.inference import EngineLoop

    engine = _make_engine(tiny_llama)
    loop = EngineLoop(engine)
    try:
        done = threading.Event()
        tokens = []
        req = loop.submit([1, 2, 3], 6,
                          on_token=lambda r, t: tokens.append(t),
                          on_finish=lambda r: done.set())
        assert done.wait(60)
        assert tokens == req.generated and len(tokens) == 6
    finally:
        loop.stop()
    engine.check_no_leaks()


def test_cancel_releases_slot_and_blocks(tiny_llama):
    """An abandoned request (client disconnect) must free its slot and
    blocks immediately so queued traffic takes its place."""
    engine = _make_engine(tiny_llama, batch_slots=1)
    done = []
    a = engine.add_request([1, 2, 3], max_new_tokens=50,
                           request_id="abandoned")
    b = engine.add_request([4, 5], max_new_tokens=3, request_id="live",
                           on_finish=lambda r: done.append(r.request_id))
    for _ in range(3):
        engine.step()                  # a holds the only slot, b queued
    assert a.state == "DECODE" and b.state == "WAITING"
    assert engine.cancel("abandoned")
    assert a.state == "FAILED" and a.error == "cancelled"
    assert not engine.cancel("abandoned")    # idempotent
    engine.run_until_idle()
    assert b.state == "FINISHED" and done == ["live"]
    engine.check_no_leaks()
    # A finished request's id may be reused (not leaked in the live set).
    engine.add_request([1], 1, request_id="abandoned")
    engine.run_until_idle()
    engine.check_no_leaks()


def test_duplicate_request_id_rejected_at_submit(tiny_llama):
    engine = _make_engine(tiny_llama)
    engine.add_request([1, 2], max_new_tokens=4, request_id="dup")
    with pytest.raises(ValueError, match="already live"):
        engine.add_request([3, 4], max_new_tokens=4, request_id="dup")
    engine.run_until_idle()
    engine.check_no_leaks()


def test_fail_all_and_submit_after_stop(tiny_llama):
    """The loop's circuit breaker: fail_all must resolve every in-flight
    and queued request (callers see the error, never a hung future), and
    a stopped loop refuses new work instead of stranding it."""
    from ray_tpu.inference import EngineLoop

    engine = _make_engine(tiny_llama, batch_slots=2)
    finished = []
    reqs = [engine.add_request([1 + i], max_new_tokens=50,
                               on_finish=lambda r: finished.append(r),
                               request_id=f"f{i}") for i in range(4)]
    engine.step()                       # two scheduled, two waiting
    assert engine.fail_all("injected failure") == 4
    assert len(finished) == 4
    assert all(r.state == "FAILED" and r.error == "injected failure"
               for r in reqs)
    engine.check_no_leaks()

    # The engine recovers: fail_all rebuilt the (donated) arena, so new
    # requests complete normally afterwards.
    recovered = engine.add_request([7, 8], max_new_tokens=3)
    engine.run_until_idle()
    assert recovered.state == "FINISHED" and len(recovered.generated) == 3
    engine.check_no_leaks()

    loop = EngineLoop(engine)
    loop.stop()
    with pytest.raises(RuntimeError, match="stopped"):
        loop.submit([1], 2)


# --------------------------------------------------------------------- #
# Radix prefix cache through the engine
# --------------------------------------------------------------------- #


def test_prefix_cache_hit_skips_prefill_no_new_programs(tiny_llama):
    """Acceptance: a repeated prompt adopts its cached blocks (skipping
    their prefill), produces bit-identical output, and compiles ZERO new
    XLA programs on the cached path."""
    model, params = tiny_llama
    engine = _make_engine(tiny_llama)              # block_size=4
    prompt = list(range(1, 10))                    # 9 tokens
    ref = _reference_generate(model, params, prompt, 6)
    a = engine.add_request(prompt, max_new_tokens=6)
    engine.run_until_idle()
    assert a.generated == ref and a.cached_tokens == 0
    s0 = engine.stats()["prefix_cache"]
    assert s0["cached_blocks"] >= 2 and s0["hits"] == 0

    b = engine.add_request(prompt, max_new_tokens=6)
    engine.run_until_idle()
    assert b.generated == ref
    # Match is block-aligned and capped one token short of the stream:
    # 8 of the 9 prompt tokens ride the cache, one still prefills.
    assert b.cached_tokens == 8
    st = engine.stats()
    assert st["prefix_cache"]["hits"] == 1
    assert st["prefix_cache"]["hit_tokens"] == 8
    assert 0.0 < st["prefix_cache"]["hit_rate"] <= 1.0
    assert_compiles_once(st, "prefill_compiles", "decode_compiles")
    engine.check_no_leaks()
    engine.drop_prefix_cache()
    engine.check_no_leaks()
    assert engine.stats()["kv"]["blocks_in_use"] == 0


def test_prefix_cache_evicts_under_arena_pressure(tiny_llama):
    """A cold cached prefix yields its blocks to live traffic: the big
    request fits by evicting cache leaves, not by preempting/failing."""
    engine = _make_engine(tiny_llama, use_jit=False, batch_slots=1,
                          num_blocks=13, block_size=4,
                          max_blocks_per_seq=12, prefill_chunk=8)
    engine.add_request(list(range(1, 9)), max_new_tokens=4)
    engine.run_until_idle()
    assert engine.stats()["prefix_cache"]["cached_blocks"] >= 2
    big = engine.add_request(list(range(100, 140)), max_new_tokens=6)
    engine.run_until_idle()
    assert big.state == "FINISHED"
    st = engine.stats()
    assert st["prefix_cache"]["evicted_blocks"] >= 1
    assert st["preemptions"] == 0
    engine.check_no_leaks()


def test_prefix_cache_live_sequence_pins_its_path(tiny_llama):
    """A decoding sequence pins its matched node: even direct maximal
    eviction pressure must not reclaim blocks its KV reads through."""
    model, params = tiny_llama
    engine = _make_engine(tiny_llama, use_jit=False)
    prompt = list(range(1, 10))
    engine.add_request(prompt, max_new_tokens=3)
    engine.run_until_idle()                        # primes the cache
    slow = engine.add_request(prompt, max_new_tokens=12)
    while slow.state != "DECODE":
        engine.step()
    assert slow.cached_tokens == 8
    assert engine.stats()["prefix_cache"]["pinned_nodes"] == 1
    engine._prefix.evict_for(10_000)               # maximal pressure
    # The pinned 2-block path survived; only unpinned tails could go.
    assert engine.stats()["prefix_cache"]["cached_blocks"] >= 2
    engine.run_until_idle()
    assert slow.generated == _reference_generate(model, params, prompt, 12)
    assert engine.stats()["prefix_cache"]["pinned_nodes"] == 0
    engine.check_no_leaks()


def test_fail_all_clears_prefix_cache_and_recovers(tiny_llama):
    """The arena rebuild invalidates every cached block's contents, so
    fail_all must drop the tree with it — and the engine re-primes."""
    engine = _make_engine(tiny_llama, use_jit=False)
    a = engine.add_request(list(range(1, 9)), max_new_tokens=4)
    engine.run_until_idle()
    assert engine.stats()["prefix_cache"]["cached_blocks"] > 0
    engine.fail_all("injected")
    st = engine.stats()
    assert st["prefix_cache"]["cached_blocks"] == 0
    assert st["kv"]["blocks_in_use"] == 0
    b = engine.add_request(list(range(1, 9)), max_new_tokens=4)
    engine.run_until_idle()
    assert b.generated == a.generated
    assert engine.stats()["prefix_cache"]["cached_blocks"] > 0
    engine.check_no_leaks()


# --------------------------------------------------------------------- #
# Speculative decoding
# --------------------------------------------------------------------- #


def test_spec_decode_lossless_and_compiles_once(tiny_llama):
    """Greedy spec decode is LOSSLESS: with the default truncated-target
    draft the output is bit-identical to the dense reference, and the
    three spec programs (draft prefill / propose / verify) each compile
    exactly once across mixed admissions."""
    model, params = tiny_llama
    engine = _make_engine(tiny_llama, spec_decode_draft_len=3)
    # Not [3, 4, 5]: its first token is a tie in bf16 (float32 logits
    # 0.6306 for 146, 0.6289 for 115), which the paged step and the
    # cacheless forward round to different sides.
    reqs = [engine.add_request([1 + i, 2 + i, 3 + i], max_new_tokens=6)
            for i in (0, 1, 3)]
    engine.run_until_idle()
    for r in reqs:
        assert r.generated == _reference_generate(model, params,
                                                  r.prompt, 6), r.request_id
    sd = engine.stats()["spec_decode"]
    assert sd["draft_len"] == 3 and sd["rounds"] > 0
    assert sum(sd["accepted_hist"]) == sd["rounds"]
    assert_compiles_once(sd, "draft_prefill_compiles", "propose_compiles",
                         "verify_compiles")
    assert_compiles_once(engine.stats(), "prefill_compiles")
    engine.check_no_leaks()
    engine.drop_prefix_cache()
    assert engine.stats()["kv"]["blocks_in_use"] == 0


def test_spec_decode_target_draft_accepts_everything(tiny_llama):
    """Upper bound: with the target itself as draft every proposal is
    accepted, so n tokens cost ceil(n / (k+1)) verify rounds, and a draft
    handed in compiles its three programs once like the built-in one."""
    model, params = tiny_llama
    engine = _make_engine(tiny_llama, spec_decode_draft_len=3,
                          draft_model=model, draft_params=params)
    r = engine.add_request([1, 2, 3, 4], max_new_tokens=8)
    engine.run_until_idle()
    assert r.generated == _reference_generate(model, params, [1, 2, 3, 4], 8)
    sd = engine.stats()["spec_decode"]
    assert sd["accept_rate"] == 1.0
    assert sd["rounds"] == 2                       # 8 tokens, k+1 = 4 each
    assert sd["accepted_hist"][3] == 2
    assert_compiles_once(sd, "draft_prefill_compiles", "propose_compiles",
                         "verify_compiles")
    engine.check_no_leaks()


@pytest.mark.slow  # ~15s eager decode; gate.sh runs the full suite
def test_spec_decode_preemption_rolls_back_without_leaks(tiny_llama):
    """Rejected drafts and preempted rows under block pressure: the
    block tables roll back cleanly (no leaked blocks) and the recomputed
    output stays bit-identical to the reference."""
    model, params = tiny_llama
    engine = _make_engine(tiny_llama, use_jit=False,
                          spec_decode_draft_len=2, batch_slots=2,
                          block_size=2, num_blocks=9,
                          max_blocks_per_seq=8, prefill_chunk=4)
    a = engine.add_request([1, 2, 3], max_new_tokens=10, request_id="a")
    b = engine.add_request([4, 5, 6], max_new_tokens=10, request_id="b")
    engine.run_until_idle()
    assert a.state == b.state == "FINISHED"
    assert engine.stats()["preemptions"] >= 1
    assert a.generated == _reference_generate(model, params, a.prompt, 10)
    assert b.generated == _reference_generate(model, params, b.prompt, 10)
    engine.check_no_leaks()
    engine.drop_prefix_cache()
    assert engine.stats()["kv"]["blocks_in_use"] == 0


# --------------------------------------------------------------------- #
# SLO classes
# --------------------------------------------------------------------- #


def test_slo_interactive_admitted_before_earlier_batch(tiny_llama):
    """Queue order is (class, arrival): a later interactive arrival
    takes the next free slot ahead of a queued batch-class request."""
    engine = _make_engine(tiny_llama, use_jit=False, batch_slots=1)
    hold = engine.add_request([1, 2], max_new_tokens=6, slo_class="batch")
    while hold.state != "DECODE":
        engine.step()
    bat = engine.add_request([3, 4], max_new_tokens=3, slo_class="batch")
    inter = engine.add_request([5, 6], max_new_tokens=3,
                               slo_class="interactive")
    assert engine.stats()["slo"] == {"reserved_slots": 0,
                                     "waiting_interactive": 1,
                                     "waiting_batch": 1}
    engine.run_until_idle()
    assert inter.first_token_at < bat.first_token_at
    engine.check_no_leaks()
    with pytest.raises(ValueError, match="slo_class"):
        engine.add_request([1], 1, slo_class="bulk")


def test_slo_reserved_slots_hold_headroom_for_interactive(tiny_llama):
    """With reserved headroom, batch-class admissions never take the
    last slot(s) — an interactive arrival lands immediately."""
    engine = _make_engine(tiny_llama, use_jit=False, batch_slots=2,
                          slo_interactive_reserved_slots=1)
    b1 = engine.add_request([1, 2], max_new_tokens=8, slo_class="batch")
    b2 = engine.add_request([3, 4], max_new_tokens=8, slo_class="batch")
    for _ in range(4):
        engine.step()
    assert b1.state in ("PREFILL", "DECODE") and b2.state == "WAITING"
    i1 = engine.add_request([5, 6], max_new_tokens=2,
                            slo_class="interactive")
    engine.run_until_idle()
    assert all(r.state == "FINISHED" for r in (b1, b2, i1))
    assert i1.first_token_at < b2.first_token_at
    engine.check_no_leaks()


def test_slo_preemption_prefers_batch_victim(tiny_llama):
    """Under block pressure the victim is the batch-class sequence even
    though it arrived FIRST (class outranks age), and both requests
    still finish with reference-exact output."""
    model, params = tiny_llama
    engine = _make_engine(tiny_llama, use_jit=False, batch_slots=2,
                          block_size=2, num_blocks=9,
                          max_blocks_per_seq=8, prefill_chunk=4)
    bat = engine.add_request([1, 2, 3], max_new_tokens=10,
                             slo_class="batch")
    inter = engine.add_request([4, 5, 6], max_new_tokens=10,
                               slo_class="interactive")
    engine.run_until_idle()
    assert engine.stats()["preemptions"] >= 1
    assert inter.preemptions == 0 and bat.preemptions >= 1
    assert inter.generated == _reference_generate(model, params,
                                                  inter.prompt, 10)
    assert bat.generated == _reference_generate(model, params,
                                                bat.prompt, 10)
    engine.check_no_leaks()


# --------------------------------------------------------------------- #
# Serve integration
# --------------------------------------------------------------------- #


def test_llm_server_generate_and_stream_through_serve(ray_start_regular):
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.inference import LLMServer

    handle = serve.run(LLMServer.options(num_replicas=1).bind(
        "tiny", 128, 8,
        engine_config={"batch_slots": 2, "block_size": 8,
                       "num_blocks": 32, "max_blocks_per_seq": 8,
                       "prefill_chunk": 8}))
    try:
        out = ray_tpu.get(handle.remote(
            {"ids": [1, 2, 3], "max_new_tokens": 5}), timeout=180)
        assert out["ids"][:3] == [1, 2, 3] and len(out["ids"]) == 8

        # Token streaming through replica/handle: one event per token as
        # produced, then the completion event.
        events = list(handle.options(stream=True).stream.remote(
            {"ids": [1, 2, 3], "max_new_tokens": 5}))
        tokens = [e["token"] for e in events if "token" in e]
        assert len(tokens) == 5
        assert events[-1]["done"] and events[-1]["ids"] == out["ids"]

        # Engine metrics ride the replica stats for the autoscaler.
        metrics = ray_tpu.get(handle.metrics.remote(None), timeout=60)
        assert metrics["requests_finished"] >= 2
        assert_compiles_once(metrics, "decode_compiles")
        # Idle arena holds only the prefix cache's donated blocks.
        assert (metrics["kv"]["blocks_in_use"]
                == metrics["prefix_cache"]["cached_blocks"])
        assert "queue_depth" in metrics and "tokens_per_sec" in metrics
    finally:
        serve.shutdown()


def test_llm_server_streams_over_http(ray_start_regular):
    import json
    import urllib.request

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.inference import LLMServer

    serve.run(LLMServer.options(num_replicas=1).bind(
        "tiny", 128, 6,
        engine_config={"batch_slots": 2, "block_size": 8,
                       "num_blocks": 32, "max_blocks_per_seq": 8,
                       "prefill_chunk": 8}))
    try:
        port = serve.http_port()
        # "stream": true switches __call__ to the token stream; items
        # arrive as chunked JSON lines through the proxy.
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/LLMServer",
            data=json.dumps({"ids": [1, 2, 3], "max_new_tokens": 4,
                             "stream": True}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as resp:
            lines = [json.loads(line) for line in resp.read().splitlines()
                     if line.strip()]
        tokens = [e["token"] for e in lines if "token" in e]
        assert len(tokens) == 4, lines
        assert lines[-1]["done"] and len(lines[-1]["ids"]) == 7

        # Unary HTTP round-trip still works next to streaming.
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/LLMServer",
            data=json.dumps({"ids": [1, 2],
                             "max_new_tokens": 3}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as resp:
            body = json.loads(resp.read())
        assert len(body["result"]["ids"]) == 5
    finally:
        serve.shutdown()
