"""What the Falcon-H1 cell's readers share. Each returns None where the
program has no such kernel or counter (the parent commit has neither)."""

from __future__ import annotations

from typing import Optional

from benchmarks import peaks, peaks_falconh1, xplane
from benchmarks.layer_metrics._common import (DECODE_MODULE, PREFILL_MODULE,
                                              kernel_label)


def is_falconh1(facts) -> bool:
    return facts.get("config", {}).get("model_type") == "falcon_h1"


def ssm_share_pct(facts) -> Optional[float]:
    """Device time of `ssd_step` and `ssd_chunk_fwd` over that of the
    engine's two programs."""
    trace = facts.get("trace")
    if not trace:
        return None
    _, decode_s = xplane.module_matching(trace, DECODE_MODULE)
    _, prefill_s = xplane.module_matching(trace, PREFILL_MODULE)
    _, kernel_s = xplane.ops_matching(
        trace, kernel_label(r"ssd_(step|chunk_fwd)"))
    total = decode_s + prefill_s
    return 100.0 * kernel_s / total if total and kernel_s else None


def kernel_roofline_pct(facts, kernel: str) -> Optional[float]:
    """Required FLOPs over peak or required bytes over peak bandwidth,
    whichever is larger, a call, over the kernel's device time a call."""
    trace = facts.get("trace")
    if not trace or not is_falconh1(facts):
        return None
    calls, seconds = xplane.ops_matching(trace, kernel_label(kernel))
    if not calls or not seconds:
        return None
    cfg = facts["config"]
    engine = cfg["engine"]
    need = {"ssd_step": lambda: peaks_falconh1.ssd_step_required(
                cfg, int(engine["batch_slots"])),
            "ssd_chunk_fwd": lambda: peaks_falconh1.ssd_chunk_fwd_required(
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
    if out is None or pre is None or not is_falconh1(facts) \
            or facts["device"]["platform"] != "tpu":
        return None         # a utilisation of the chip's peak, or nothing
    cfg, traffic = facts["config"], facts["traffic"]
    prompt = (traffic["prompt"]["min"] + traffic["prompt"]["max"]) / 2.0
    output = (traffic["output"]["min"] + traffic["output"]["max"]) / 2.0
    flops = out * peaks_falconh1.serve_flops_per_token(
        cfg, prompt + output / 2.0, True) \
        + pre * peaks_falconh1.serve_flops_per_token(cfg, prompt / 2.0,
                                                     False)
    return 100.0 * flops / peaks.peaks_for(
        facts["device"]["kind"])["flops_per_s"]
