"""What the Kanana-2 cell's readers share. Each returns None where the
configuration is not a `deepseek_v3` one, or the program has no such
kernel or counter (the parent commit has neither)."""

from __future__ import annotations

from typing import Optional

from benchmarks import peaks, peaks_kanana2, xplane
from benchmarks.layer_metrics._common import (DECODE_MODULE, PREFILL_MODULE,
                                              kernel_label)
from benchmarks.layer_metrics._qwen3next import _roofline_pct

LATENT_KERNELS = r"latent_(decode|prefill)"
MOE_KERNEL = "moe_gmm"


def is_kanana2(facts) -> bool:
    return facts.get("config", {}).get("model_type") == "deepseek_v3"


def _window(facts, name: str) -> Optional[dict]:
    return (facts.get("counters") or {}).get(f"window_{name}")


def _programs(facts):
    """(decode executions, prefill executions, their device seconds) in
    the trace."""
    trace = facts["trace"]
    dec, dec_s = xplane.module_matching(trace, DECODE_MODULE)
    pre, pre_s = xplane.module_matching(trace, PREFILL_MODULE)
    return dec, pre, dec_s + pre_s


def share_pct(facts, kernels: str) -> Optional[float]:
    """Device time of `kernels` over that of the engine's two programs."""
    trace = facts.get("trace")
    if not trace or not is_kanana2(facts):
        return None
    _, _, total = _programs(facts)
    _, kernel_s = xplane.ops_matching(trace, kernel_label(kernels))
    return 100.0 * kernel_s / total if total and kernel_s else None


def _shapes(facts):
    """(live decode rows a step, mean context, mean question) of the
    window, from the engine's and the client's counts."""
    counters = facts["counters"]
    dec, _, _ = _programs(facts)
    emitted = counters.get("tokens_emitted_in_trace")
    if not dec or emitted is None or not counters.get("mean_context"):
        return None
    rows = (emitted - counters.get("first_tokens_in_trace", 0)) / dec
    traffic = facts["traffic"]
    question = (traffic["prompt"]["min"] + traffic["prompt"]["max"]) / 2.0
    return rows, float(counters["mean_context"]), question


def latent_roofline_pct(facts, kernel: str) -> Optional[float]:
    """The traced calls' required FLOPs over peak or required bytes over
    peak bandwidth, whichever is larger, over the kernel's device time."""
    trace = facts.get("trace")
    if not trace or not is_kanana2(facts):
        return None
    calls, seconds = xplane.ops_matching(trace, kernel_label(kernel))
    shapes = _shapes(facts)
    if not calls or not seconds or shapes is None:
        return None
    rows, context, question = shapes
    cfg = facts["config"]
    if kernel == "latent_decode":
        need = peaks_kanana2.latent_decode_required(cfg, rows, context)
    else:
        need = peaks_kanana2.latent_prefill_required(
            cfg, question, float(facts["traffic"]["document_len"]))
    return _roofline_pct(facts, need, calls, seconds)


def _fused_share(facts) -> float:
    """The share of the window's decode executions that carried a prefill
    chunk, by the engine's own ledger (`window_steps`: `decode` counts a
    fused execution too, `chunks_aboard` those alone). The trace cannot
    tell: both programs are `jit_decode_fn`. 0 where the program has no
    fused step."""
    steps = _window(facts, "steps") or {}
    return steps.get("chunks_aboard", 0) / steps["decode"] \
        if steps.get("decode") else 0.0


def serve_moe_gmm_roofline_pct(facts) -> Optional[float]:
    """The grouped products' required work in the traced executions, each
    counted ONCE under the kind whose counters hold its rows (the window's
    own: assignments and experts that drew a row an execution), over their
    device time. The model books a decode step that carried a chunk whole
    under `prefill`, so that kind's work is times the traced chunks alone
    plus the traced decode executions' fused share, and the `decode`
    kind's times the rest."""
    trace, moe = facts.get("trace"), _window(facts, "moe")
    if not trace or not moe or not is_kanana2(facts):
        return None
    _, seconds = xplane.ops_matching(trace, kernel_label(MOE_KERNEL))
    dec, pre, _ = _programs(facts)
    if not seconds or not (dec or pre):
        return None
    cfg = facts["config"]
    fused = dec * _fused_share(facts)
    flops = nbytes = 0.0
    for runs, kind in ((dec - fused, "decode"), (pre + fused, "prefill")):
        need = peaks_kanana2.moe_gmm_required(
            cfg, moe[kind]["assignments_per_step"],
            moe[kind]["experts_drawn_per_step"])
        flops += runs * moe["layers"] * need["flops"]
        nbytes += runs * moe["layers"] * need["bytes"]
    return _roofline_pct(facts, {"flops": flops, "bytes": nbytes}, 1, seconds)


def load_max_over_mean(facts) -> Optional[float]:
    moe = _window(facts, "moe")
    if not moe or not moe["decode"]["steps"]:
        return None
    return moe["decode"]["load_max_over_mean"]


def prefix_hit_pct(facts) -> Optional[float]:
    prefix = _window(facts, "prefix")
    if not prefix or not prefix.get("prompt_tokens") \
            or not is_kanana2(facts):
        return None
    return 100.0 * prefix["hit_tokens"] / prefix["prompt_tokens"]


def _rates(facts):
    client = facts.get("client") or {}
    out, pre = client.get("out_tok_s"), client.get("prefill_tok_s")
    counters = facts.get("counters") or {}
    if out is None or pre is None or not is_kanana2(facts) \
            or facts["device"]["platform"] != "tpu" \
            or not counters.get("mean_context"):
        return None         # a utilisation of the chip's peak, or nothing
    return out, pre, float(counters["mean_context"])


def serve_mfu_pct(facts) -> Optional[float]:
    """Tokens a second through decode and through prefill (client side,
    the untraced part of the window) times a token's model FLOPs at the
    window's mean context, over the chip's bf16 peak."""
    rates = _rates(facts)
    if rates is None:
        return None
    out, pre, context = rates
    cfg = facts["config"]
    flops = out * peaks_kanana2.serve_flops_per_token(cfg, True, context) \
        + pre * peaks_kanana2.serve_flops_per_token(
            cfg, False, float(facts["traffic"]["document_len"]))
    return 100.0 * flops / peaks.peaks_for(
        facts["device"]["kind"])["flops_per_s"]


def serve_membw_pct(facts) -> Optional[float]:
    """The executions' required bytes a second over the chip's HBM
    bandwidth, each execution counted ONCE (`peaks_kanana2.step_bytes` at
    the window's counts). Executions that hold a chunk a second: one a
    request (client prefill tokens a second over the mean question); the
    rows of one are the `prefill` kind's (the question's, and where the
    chunk rode in a decode step that step's live rows too: its weights are
    read once for both). Plain decode steps a second: the client tokens a
    second that no such execution's decode rows emitted, over the live
    rows of a `decode`-kind step."""
    rates, moe = _rates(facts), _window(facts, "moe")
    if rates is None or not moe or not moe["decode"]["steps"]:
        return None
    out, pre, context = rates
    cfg, traffic = facts["config"], facts["traffic"]
    top_k = int(cfg["num_experts_per_tok"])
    rows = moe["decode"]["assignments_per_step"] / top_k
    question = (traffic["prompt"]["min"] + traffic["prompt"]["max"]) / 2.0
    if not rows:
        return None
    # decode rows aboard an execution that holds a chunk (mean; 0 where
    # every chunk ran alone)
    aboard = max(0.0, moe["prefill"]["assignments_per_step"] / top_k
                 - question)
    chunks_s = pre / question
    per_s = ((out - chunks_s * aboard) / rows) * peaks_kanana2.step_bytes(
        cfg, rows, rows * context, moe["decode"]["experts_drawn_per_step"],
        rows) \
        + chunks_s * peaks_kanana2.step_bytes(
            cfg, question + aboard,
            float(traffic["document_len"]) + question + aboard * context,
            moe["prefill"]["experts_drawn_per_step"], 1.0 + aboard)
    return 100.0 * per_s / peaks.peaks_for(
        facts["device"]["kind"])["hbm_bytes_per_s"]
