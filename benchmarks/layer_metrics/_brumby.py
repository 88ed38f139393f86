"""What the Brumby cell's readers share. Each returns None where the
configuration is not a `brumby` one, or the program has no such kernel or
counter (the parent commit has neither)."""

from __future__ import annotations

from typing import Optional

from benchmarks import peaks, peaks_brumby, xplane
from benchmarks.layer_metrics._common import (DECODE_MODULE, PREFILL_MODULE,
                                              kernel_label)


def is_brumby(facts) -> bool:
    return facts.get("config", {}).get("model_type") == "brumby"


def retention_share_pct(facts) -> Optional[float]:
    """Device time of `retention_step` and `retention_chunk_fwd` over that
    of the engine's two programs."""
    trace = facts.get("trace")
    if not trace or not is_brumby(facts):
        return None
    _, decode_s = xplane.module_matching(trace, DECODE_MODULE)
    _, prefill_s = xplane.module_matching(trace, PREFILL_MODULE)
    _, kernel_s = xplane.ops_matching(
        trace, kernel_label(r"retention_(step|chunk_fwd)"))
    total = decode_s + prefill_s
    return 100.0 * kernel_s / total if total and kernel_s else None


def kernel_roofline_pct(facts, kernel: str) -> Optional[float]:
    """Required FLOPs over peak or required bytes over peak bandwidth,
    whichever is larger, a call, over the kernel's device time a call."""
    trace = facts.get("trace")
    if not trace or not is_brumby(facts):
        return None
    calls, seconds = xplane.ops_matching(trace, kernel_label(kernel))
    if not calls or not seconds:
        return None
    cfg = facts["config"]
    engine = cfg["engine"]
    need = {"retention_step":
            lambda: peaks_brumby.retention_step_required(
                cfg, int(engine["batch_slots"])),
            "retention_chunk_fwd":
            lambda: peaks_brumby.retention_chunk_fwd_required(
                cfg, 1, int(engine["prefill_chunk"]))}[kernel]()
    floor = peaks.roofline_floor_s(
        need["flops"], need["bytes"],
        peaks.peaks_for(facts["device"]["kind"]))
    return 100.0 * floor["floor_s"] * calls / seconds


def serve_mfu_pct(facts) -> Optional[float]:
    """Tokens a second through decode and through prefill (client side,
    the untraced part of the window) times a token's model FLOPs, over the
    chip's bf16 peak."""
    client = facts.get("client") or {}
    out, pre = client.get("out_tok_s"), client.get("prefill_tok_s")
    if out is None or pre is None or not is_brumby(facts) \
            or facts["device"]["platform"] != "tpu":
        return None         # a utilisation of the chip's peak, or nothing
    cfg = facts["config"]
    flops = out * peaks_brumby.serve_flops_per_token(cfg, True) \
        + pre * peaks_brumby.serve_flops_per_token(cfg, False)
    return 100.0 * flops / peaks.peaks_for(
        facts["device"]["kind"])["flops_per_s"]
