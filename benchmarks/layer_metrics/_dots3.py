"""What the dots3 cell's readers share. Each returns None where the
configuration is not a `dots3_note` one, or the program has no such
kernel, scope or counter (the parent commit has none)."""

from __future__ import annotations

from typing import Optional

from benchmarks import peaks, peaks_dots3, xplane
from benchmarks.layer_metrics._common import (DECODE_MODULE, PREFILL_MODULE,
                                              kernel_label)
from benchmarks.layer_metrics._qwen3next import _roofline_pct

DSA_SCOPES = ("dsa_index", "dsa_select", "dsa_gather", "dsa_attend")
MOE_KERNEL = "moe_gmm"


def is_dots3(facts) -> bool:
    return facts.get("config", {}).get("model_type") == "dots3_note"


def _window(facts, name: str) -> Optional[dict]:
    return (facts.get("counters") or {}).get(f"window_{name}")


def _programs(facts):
    trace = facts["trace"]
    dec, dec_s = xplane.module_matching(trace, DECODE_MODULE)
    pre, pre_s = xplane.module_matching(trace, PREFILL_MODULE)
    return dec, pre, dec_s + pre_s


def _scopes(facts) -> Optional[dict]:
    trace = facts.get("trace")
    if not trace or not is_dots3(facts):
        return None
    scopes = trace.get("scopes") or {}
    return None if "error" in scopes else scopes or None


def scope_share_pct(facts, names) -> Optional[float]:
    """Device time of the ops under the model's scopes `names` over that
    of the engine's two programs."""
    scopes = _scopes(facts)
    if not scopes:
        return None
    _, _, total = _programs(facts)
    seconds = sum(scopes[s][1] for s in names if s in scopes)
    return 100.0 * seconds / total if total and seconds else None


def kernel_share_pct(facts, kernel: str) -> Optional[float]:
    trace = facts.get("trace")
    if not trace or not is_dots3(facts):
        return None
    _, _, total = _programs(facts)
    _, seconds = xplane.ops_matching(trace, kernel_label(kernel))
    return 100.0 * seconds / total if total and seconds else None


def _shapes(facts) -> Optional[dict]:
    """The traced executions' shapes by the window's own counts: live rows
    a decode step, a token's mean visible keys, a chunk's mean queries."""
    dsa, steps = _window(facts, "dsa"), _window(facts, "steps")
    if not dsa or not steps or not steps.get("decode") \
            or not dsa["decode"]["queries"]:
        return None
    layers = dsa["full_layers"]
    out = {"rows": dsa["decode"]["queries"] / steps["decode"],
           "context": dsa["decode"]["keys_visible"]
           / (dsa["decode"]["queries"] * layers),
           "chosen": dsa["decode"]["keys_chosen"]
           / (dsa["decode"]["queries"] * layers),
           "chunks": steps.get("prefill", 0), "chunk_queries": 0.0,
           "chunk_context": 0.0, "chunk_chosen": 0.0}
    if steps.get("prefill") and dsa["prefill"]["queries"]:
        q = dsa["prefill"]["queries"]
        out.update(chunk_queries=q / steps["prefill"],
                   chunk_context=dsa["prefill"]["keys_visible"] / (q * layers),
                   chunk_chosen=dsa["prefill"]["keys_chosen"] / (q * layers))
    return out


def _traced_need(facts, per_call):
    """Sum of `per_call(sequences, queries, context, chosen)` over the
    traced decode and prefill executions (each layer's call once)."""
    shapes = _shapes(facts)
    if shapes is None:
        return None
    dec, pre, _ = _programs(facts)
    flops = nbytes = 0.0
    for runs, args in (
            (dec, (shapes["rows"], shapes["rows"], shapes["context"],
                   shapes["chosen"])),
            (pre, (1.0, shapes["chunk_queries"], shapes["chunk_context"],
                   shapes["chunk_chosen"]))):
        if not runs or not args[1]:
            continue
        need = per_call(*args)
        flops += runs * need["flops"]
        nbytes += runs * need["bytes"]
    return {"flops": flops, "bytes": nbytes} if nbytes else None


def index_roofline_pct(facts) -> Optional[float]:
    trace = facts.get("trace")
    if not trace or not is_dots3(facts):
        return None
    _, seconds = xplane.ops_matching(trace, kernel_label("dsa_index"))
    cfg = facts["config"]
    layers = sum(k == peaks_dots3.FULL for k in peaks_dots3.kinds(cfg))
    need = _traced_need(facts, lambda s, q, c, n: {
        k: layers * v for k, v in
        peaks_dots3.index_required(cfg, s, q, c).items()})
    if not seconds or need is None:
        return None
    return _roofline_pct(facts, need, 1, seconds)


def sparse_attn_roofline_pct(facts) -> Optional[float]:
    scopes = _scopes(facts)
    if not scopes:
        return None
    seconds = sum(scopes[s][1] for s in ("dsa_gather", "dsa_attend")
                  if s in scopes)
    cfg = facts["config"]
    layers = sum(k == peaks_dots3.FULL for k in peaks_dots3.kinds(cfg))
    need = _traced_need(facts, lambda s, q, c, n: {
        k: layers * v for k, v in
        peaks_dots3.sparse_attn_required(cfg, q, n).items()})
    if not seconds or need is None:
        return None
    return _roofline_pct(facts, need, 1, seconds)


def window_latent_roofline_pct(facts) -> Optional[float]:
    scopes = _scopes(facts)
    if not scopes or "window_attn" not in scopes:
        return None
    seconds = scopes["window_attn"][1]
    cfg = facts["config"]
    layers = sum(k != peaks_dots3.FULL for k in peaks_dots3.kinds(cfg))
    need = _traced_need(facts, lambda s, q, c, n: {
        k: layers * v for k, v in
        peaks_dots3.window_latent_required(cfg, s, q, c).items()})
    if not seconds or need is None:
        return None
    return _roofline_pct(facts, need, 1, seconds)


def selected_pct(facts) -> Optional[float]:
    """Keys the selection chose over keys visible, the window's decode
    steps (program counters)."""
    dsa = _window(facts, "dsa")
    if not dsa or not is_dots3(facts) or not dsa["decode"]["keys_visible"]:
        return None
    return 100.0 * dsa["decode"]["keys_chosen"] \
        / dsa["decode"]["keys_visible"]


def window_blocks_released_per_s(facts) -> Optional[float]:
    kv = _window(facts, "kv")
    if not kv or not is_dots3(facts) or not kv.get("seconds"):
        return None
    return kv["window_blocks_released"] / kv["seconds"]


def _moe(facts, kind: str = "decode") -> Optional[dict]:
    moe = _window(facts, "moe")
    if not moe or not is_dots3(facts) or not moe[kind]["steps"]:
        return None
    return moe


def experts_drawn_per_step(facts) -> Optional[float]:
    moe = _moe(facts)
    return moe["decode"]["experts_drawn_per_step"] if moe else None


def moe_gmm_roofline_pct(facts) -> Optional[float]:
    trace, moe = facts.get("trace"), _moe(facts)
    if not trace or not moe:
        return None
    _, seconds = xplane.ops_matching(trace, kernel_label(MOE_KERNEL))
    dec, pre, _ = _programs(facts)
    if not seconds or not (dec or pre):
        return None
    cfg = facts["config"]
    flops = nbytes = 0.0
    for runs, kind in ((dec, "decode"), (pre, "prefill")):
        if not runs or not moe[kind]["steps"]:
            continue
        need = peaks_dots3.moe_gmm_required(
            cfg, moe[kind]["assignments_per_step"],
            moe[kind]["experts_drawn_per_step"])
        flops += runs * moe["layers"] * need["flops"]
        nbytes += runs * moe["layers"] * need["bytes"]
    return _roofline_pct(facts, {"flops": flops, "bytes": nbytes}, 1, seconds)


def prefix_hit_pct(facts) -> Optional[float]:
    prefix = _window(facts, "prefix")
    if not prefix or not prefix.get("prompt_tokens") or not is_dots3(facts):
        return None
    return 100.0 * prefix["hit_tokens"] / prefix["prompt_tokens"]


def _rates(facts) -> Optional[dict]:
    """The untraced part of the window, client side, and the window's own
    shapes; None off the chip (a share of the chip's peak, or nothing)."""
    client = facts.get("client") or {}
    shapes = _shapes(facts) if is_dots3(facts) else None
    got = {"out": client.get("out_tok_s"), "pre": client.get("prefill_tok_s"),
           "requests": client.get("requests_s")}
    if shapes is None or any(v is None for v in got.values()) \
            or facts["device"]["platform"] != "tpu":
        return None
    return {**got, **shapes}


def serve_mfu_pct(facts) -> Optional[float]:
    """Tokens a second through decode steps and through chunks times a
    token's required FLOPs at the contexts the window's counters saw, over
    the chip's bf16 peak: the WHOLE window, chunks and decode steps."""
    r = _rates(facts)
    if r is None:
        return None
    cfg = facts["config"]
    flops = r["out"] * peaks_dots3.serve_flops_per_token(cfg, r["context"]) \
        + r["pre"] * peaks_dots3.serve_flops_per_token(
            cfg, r["chunk_context"] or r["context"])
    return 100.0 * flops / peaks.peaks_for(
        facts["device"]["kind"])["flops_per_s"]


def serve_membw_pct(facts) -> Optional[float]:
    """The executions' required bytes a second over the chip's HBM
    bandwidth: decode steps a second (emitted tokens over a step's live
    rows) and chunks a second (a request each), `peaks_dots3.step_bytes`
    at the window's counts."""
    r, moe = _rates(facts), _moe(facts)
    if r is None or not moe or not r["rows"]:
        return None
    cfg = facts["config"]
    per_s = (r["out"] / r["rows"]) * peaks_dots3.step_bytes(
        cfg, r["rows"], r["rows"], r["context"],
        moe["decode"]["experts_drawn_per_step"])
    if r["chunk_queries"] and moe["prefill"]["steps"]:
        per_s += r["requests"] * peaks_dots3.step_bytes(
            cfg, 1.0, r["chunk_queries"], r["chunk_context"],
            moe["prefill"]["experts_drawn_per_step"])
    return 100.0 * per_s / peaks.peaks_for(
        facts["device"]["kind"])["hbm_bytes_per_s"]
