"""A second KIND of paged state whose pages age out (docs/INFERENCE.md
finding (j)): the window pool and the windowed radix cache alone, then
through the engine with `Dots3.tiny()`: release behind the window at every
claim, adoption at a boundary that holds window pages and refusal without,
a node split, eviction of a cold document, preemption in mid-decode and
recompute, cancel and `fail_all`, no leak in either pool; what a model
with ONE kind is handed stays as it was; speculation is refused."""

import numpy as np
import pytest

from ray_tpu.inference.engine import EngineConfig, InferenceEngine
from ray_tpu.inference.kv_cache import (TRASH_BLOCK, BlockManager,
                                        RadixPrefixCache,
                                        WindowBlockManager,
                                        WindowedRadixCache,
                                        window_tail_blocks)


@pytest.mark.parametrize("window,block,tail", [
    (513, 128, 5), (513, 16, 33), (17, 8, 3), (9, 8, 2), (1, 8, 1),
    (512, 128, 5), (514, 128, 6)])
def test_the_tail_a_cached_prefix_keeps(window, block, tail):
    assert window_tail_blocks(window, block) == tail


def test_release_below_gives_pages_back_once():
    pool = WindowBlockManager(12, 4, 9)
    pool.register("a")
    assert pool.ensure("a", 30) and pool.pages_held("a") == 8
    assert pool.release_below("a", 3) == 3 and pool.num_free() == 6
    assert pool.release_below("a", 3) == 0
    assert pool.block_table("a")[:3] == [TRASH_BLOCK] * 3
    # growing appends; the released entries stay where they were
    assert pool.ensure("a", 36) and len(pool.block_table("a")) == 9
    # a page another table shares goes back when both let go
    pool.register_with_blocks("b", pool.block_table("a"))
    assert pool.release_below("a", 5) == 2 and pool.num_free() == 5
    assert pool.free("b") == 2
    pool.check_consistency()
    assert pool.free("a") == 4 and pool.blocks_in_use() == 0
    assert pool.stats()["window_blocks_released"] == 5
    with pytest.raises(ValueError, match="not live"):
        pool.register_with_blocks("c", [0, 0, 7])


class Books:
    """The two pools and the tree, driven as the engine drives them."""

    def __init__(self, blocks=64, wblocks=24, bs=4, window=9):
        self.bm = BlockManager(blocks, bs)
        self.wbm = WindowBlockManager(wblocks, bs, window)
        self.tree = WindowedRadixCache(self.bm, self.wbm)
        self.bs = bs

    def run(self, tokens, keep=False):
        """A sequence from admission to its finish: (tokens adopted, window
        pages it held at the end)."""
        bs, bm, wbm, tree = self.bs, self.bm, self.wbm, self.tree
        blocks, node = tree.match(tokens[:(len(tokens) - 1) // bs * bs])
        if blocks:
            bm.register_with_blocks("s", blocks)
            wbm.register_with_blocks("s", tree.window_table(node))
            tree.pin(node)
        else:
            bm.register("s")
            wbm.register("s")
        for p in range(len(blocks) * bs, len(tokens)):
            wbm.release_below("s", p // bs - wbm.tail)
            assert bm.ensure("s", p + 1) and wbm.ensure("s", p + 1)
            assert wbm.pages_held("s") <= wbm.tail + 1
        nb = len(tokens) // bs
        tree.insert(tokens[:nb * bs], bm.block_table("s")[:nb],
                    wbm.block_table("s")[:nb])
        tree.unpin(node)
        held = wbm.pages_held("s")
        bm.free("s")
        wbm.free("s")
        self.check()
        return len(blocks) * bs, held

    def check(self):
        self.bm.check_consistency()
        self.wbm.check_consistency()
        self.tree.check_consistency()
        assert self.bm.blocks_in_use() == self.tree.cached_blocks()
        assert self.wbm.blocks_in_use() == self.tree.cached_window_blocks()


def test_adoption_only_where_window_pages_are_held():
    b = Books()
    doc = list(range(40))
    assert b.run(doc) == (0, 4)
    # the whole document is adopted, with the window pages of its tail
    assert b.run(doc + [99] * 9) == (40, 4)
    assert b.tree.stats()["window_adoptions_refused"] == 0
    # a sequence that leaves the document inside its node SPLITS it at
    # the donation: the top half ends where no window page is held
    assert b.run(doc[:20] + [7] * 20) == (0, 4)
    assert b.tree.stats()["window_adoptions_refused"] == 1
    # ... so a match that ends at the split falls back to the root, and is
    # counted; its own donation then makes the top half whole
    assert b.run(doc[:20] + [8]) == (0, 4)
    assert b.tree.stats()["window_adoptions_refused"] == 2
    assert b.run(doc[:20] + [8] * 5) == (20, 4)
    # the bottom half kept the pages that end where it ends
    assert b.run(doc + [5]) == (40, 4)
    # a match that ends INSIDE a node adopts the deepest end above it
    assert b.run(doc[:20] + [7] * 9) == (20, 4)
    assert b.tree.stats()["window_adoptions_refused"] == 3


def test_a_cached_prefix_keeps_its_tail_and_no_more():
    b = Books(window=9)                       # tail 3 blocks of 4
    b.run(list(range(40)))
    assert b.tree.cached_blocks() == 10
    assert b.tree.cached_window_blocks() == b.wbm.tail == 3
    b.run(list(range(40)) + [3] * 12)         # a child of three blocks
    # the child's tail is its own three blocks: six distinct pages
    assert b.tree.cached_window_blocks() == 6
    b.run(list(range(40)) + [4] * 4)          # a child of one block
    # ... whose tail reaches two blocks into the document's, and shares them
    assert b.tree.cached_window_blocks() == 7


def test_a_cold_document_is_evicted_from_both_pools():
    b = Books(blocks=64, wblocks=10, window=9)
    b.run(list(range(40)))
    b.run([50 + t for t in range(40)])
    assert b.tree.cached_window_blocks() == 6
    # the window pool is what runs out: the coldest leaf goes, from both
    before = b.bm.num_free()
    assert b.tree.evict_for(2, window=True) == 3
    assert b.bm.num_free() == before + 10
    assert b.tree.match(list(range(40)))[0] == []
    assert len(b.tree.match([50 + t for t in range(40)])[0]) == 10
    b.check()
    assert b.tree.clear() == 10 and b.wbm.blocks_in_use() == 0


def test_the_base_cache_is_what_it_was():
    """One kind: `RadixPrefixCache` splits on a match and knows no window."""
    bm = BlockManager(32, 4)
    tree = RadixPrefixCache(bm)
    bm.register("s")
    bm.ensure("s", 16)
    tree.insert(list(range(16)), bm.block_table("s"))
    blocks, node = tree.match(list(range(8)) + [9] * 8)
    assert len(blocks) == 2 and tree.stats()["nodes"] == 2
    assert "cached_window_blocks" not in tree.stats()


# --------------------------------------------------------------------------- #
# through the engine
# --------------------------------------------------------------------------- #


@pytest.fixture(scope="module")
def tiny():
    import jax

    from ray_tpu.models.dots3 import Dots3, Dots3Config

    model = Dots3(Dots3Config.tiny())        # window 9, index_topk 16
    return model, model.init(jax.random.PRNGKey(0))


def engine_of(tiny, **kw):
    model, params = tiny
    cfg = dict(batch_slots=4, block_size=8, num_blocks=64,
               max_blocks_per_seq=12, prefill_chunk=16, window_blocks=24)
    return InferenceEngine(EngineConfig(**{**cfg, **kw}), model=model,
                           params=params)


_ROOMY = {}


def greedy(tiny, prompt, n):
    """What an UNDISTURBED run emits: a roomy engine with no prefix cache,
    nothing adopted, released early or preempted (`tests/test_dots3.py`
    holds the model itself to the plain reference)."""
    if "engine" not in _ROOMY:
        _ROOMY["engine"] = engine_of(tiny, num_blocks=128, window_blocks=128,
                                     prefix_cache_enabled=False)
    req = _ROOMY["engine"].add_request(list(prompt), n)
    _ROOMY["engine"].run_until_idle()
    assert req.cached_tokens == 0 and req.preemptions == 0
    return list(req.generated)


def prompt(n, seed):
    return [int(t) for t in np.random.default_rng(seed).integers(1, 96, n)]


def test_two_pools_release_adopt_and_do_not_leak(tiny):
    engine = engine_of(tiny)
    doc = prompt(48, 0)
    engine.add_request(doc, 1)
    engine.run_until_idle()
    kinds = engine.stats()["kv_kinds"]
    assert kinds["full"]["cached"] == 6
    assert kinds["window"]["cached"] == kinds["window"]["tail_blocks"] == 2
    assert kinds["window"]["window_blocks_released"] == 2
    reqs = [engine.add_request(doc + prompt(n, n), 10)
            for n in (5, 9, 13, 3, 7)]
    peak = 0
    while engine.has_work():
        engine.step()
        win = engine._wbm
        live = [r for r in reqs if win.registered(r.request_id)]
        # a live sequence holds its tail, its block and its chunk's
        assert all(win.pages_held(r.request_id) <= win.tail + 3
                   for r in live)
        peak = max(peak, engine.stats()["kv_kinds"]["window"]["in_use"])
    for r in reqs:
        assert r.cached_tokens == 48 and r.cached_window_tokens == 16
        assert r.generated == greedy(tiny, r.prompt, 10)
    engine.check_no_leaks()
    kinds = engine.stats()["kv_kinds"]
    assert kinds["window"]["window_blocks_released"] > 2
    assert kinds["window"]["adoptions_refused"] == 0
    assert kinds["window"]["peak"] >= peak > kinds["window"]["cached"] - 1
    assert kinds["window"]["in_use"] == kinds["window"]["cached"]
    assert engine.stats()["paged_attn"] == {
        "decode": "reference: platform cpu",
        "prefill": "reference: platform cpu"}


def test_a_match_without_window_pages_is_refused_not_adopted(tiny):
    engine = engine_of(tiny)
    doc = prompt(48, 1)
    engine.add_request(doc, 1)
    engine.run_until_idle()
    # diverges inside the document's node: donated, the node splits
    a = engine.add_request(doc[:24] + prompt(10, 2), 4)
    engine.run_until_idle()
    assert a.cached_tokens == 0
    # ends at the split (24 tokens matched in the `full` kind, no window
    # page held there): nothing adopted, everything recomputed, same tokens
    b = engine.add_request(doc[:24] + prompt(3, 3), 6)
    engine.run_until_idle()
    assert b.cached_tokens == 0 and b.cached_window_tokens == 0
    assert b.generated == greedy(tiny, b.prompt, 6)
    assert engine.stats()["kv_kinds"]["window"]["adoptions_refused"] >= 2
    # the bottom half kept its pages: the whole document is adopted still
    c = engine.add_request(doc + prompt(4, 4), 5)
    engine.run_until_idle()
    assert c.cached_tokens == 48 and c.generated == greedy(tiny, c.prompt, 5)
    engine.check_no_leaks()


def test_preemption_in_mid_decode_recomputes_the_same_tokens(tiny):
    # a window pool that holds three live sequences, not four
    engine = engine_of(tiny, window_blocks=10, prefix_cache_enabled=False)
    reqs = [engine.add_request(prompt(20, 10 + i), 24) for i in range(4)]
    engine.run_until_idle()
    assert engine.stats()["preemptions"] > 0
    assert sum(r.preemptions for r in reqs) == engine.stats()["preemptions"]
    for r in reqs:
        assert r.generated == greedy(tiny, r.prompt, 24), r.preemptions
    engine.check_no_leaks()
    assert engine.stats()["kv_kinds"]["window"]["in_use"] == 0


def test_a_cold_document_leaves_when_the_window_pool_is_short(tiny):
    engine = engine_of(tiny, window_blocks=12)
    for seed in range(4):                   # four documents, two pages each
        engine.add_request(prompt(40, 20 + seed), 1)
        engine.run_until_idle()
    assert engine.stats()["kv_kinds"]["window"]["cached"] == 8
    r = engine.add_request(prompt(30, 30), 8)
    engine.run_until_idle()
    assert r.generated == greedy(tiny, r.prompt, 8)
    stats = engine.stats()
    assert stats["prefix_cache"]["evicted_window_blocks"] > 0
    assert stats["preemptions"] == 0
    engine.check_no_leaks()


def test_cancel_and_fail_all_give_back_both_kinds(tiny):
    engine = engine_of(tiny)
    doc = prompt(48, 40)
    engine.add_request(doc, 1)
    engine.run_until_idle()
    a = engine.add_request(doc + prompt(5, 41), 30)
    b = engine.add_request(prompt(20, 42), 30)
    for _ in range(6):
        engine.step()
    assert engine._wbm.registered(a.request_id)
    assert engine.cancel(a.request_id)
    assert not engine._wbm.registered(a.request_id)
    assert engine.fail_all("stop") == 1 and b.error == "stop"
    engine.check_no_leaks()
    kinds = engine.stats()["kv_kinds"]
    assert kinds["window"]["in_use"] == kinds["full"]["in_use"] == 0
    # the arenas are new: the engine serves again, from nothing
    c = engine.add_request(doc + prompt(5, 43), 4)
    engine.run_until_idle()
    assert c.cached_tokens == 0 and c.generated == greedy(tiny, c.prompt, 4)


def test_the_prefill_span_says_what_was_adopted_of_each_kind(tiny):
    from ray_tpu.core.config import GLOBAL_CONFIG
    from ray_tpu.observability import tracing

    engine = engine_of(tiny)
    doc = prompt(48, 50)
    engine.add_request(doc, 1)
    engine.run_until_idle()
    GLOBAL_CONFIG._overrides["tracing_enabled"] = True
    tracing.refresh_from_config()
    tracing.RECORDER.drain()
    try:
        with tracing.get_tracer().start_span("client.request") as root:
            engine.add_request(doc + prompt(5, 51), 3)
        engine.run_until_idle()
        spans, _ = tracing.RECORDER.drain()
    finally:
        GLOBAL_CONFIG._overrides.pop("tracing_enabled", None)
        tracing.refresh_from_config()
        tracing.RECORDER.drain()
    (span,) = [s for s in spans if s["trace_id"] == root.trace_id
               and s["name"] == "engine.prefill"]
    assert span["attrs"]["adopted_tokens"] == {"full": 48, "window": 16}


def test_speculation_and_a_missing_pool_are_refused(tiny):
    with pytest.raises(ValueError, match="ageing kind"):
        engine_of(tiny, spec_decode_draft_len=2)
    with pytest.raises(ValueError, match="window_blocks"):
        engine_of(tiny, window_blocks=0)


def test_a_model_with_one_kind_is_handed_what_it_was(tiny):
    """No second pool, one table an array, the class itself, no `kv_kinds`
    in `stats()`; a model that states kinds gets the subclass."""
    from ray_tpu.inference.api import preset_model

    model, params = preset_model("tiny", 256)
    engine = InferenceEngine(
        EngineConfig(batch_slots=2, block_size=4, num_blocks=32,
                     max_blocks_per_seq=8, prefill_chunk=8),
        model=model, params=params)
    assert model.cache_kinds is None and type(engine) is InferenceEngine
    assert not hasattr(engine, "_wbm")
    assert isinstance(engine._prefix, RadixPrefixCache)
    assert not isinstance(engine._prefix, WindowedRadixCache)
    assert isinstance(engine._block_table_rows([None]), np.ndarray)
    assert "kv_kinds" not in engine.stats()
    kinds = engine_of(tiny)
    assert type(kinds) is not InferenceEngine
    assert isinstance(kinds, InferenceEngine)
    assert set(kinds._block_table_rows([None])) == {"full", "window"}
