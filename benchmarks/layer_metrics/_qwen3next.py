"""What the Qwen3-Next cell's readers share. Each returns None where the
program has no such kernel or counter (the parent commit has neither)."""

from __future__ import annotations

from typing import Optional

from benchmarks import peaks, peaks_qwen3next, xplane
from benchmarks.layer_metrics._common import STEP_MODULE, kernel_label

MOE_KERNELS = r"moe_gmm(_dlhs|_drhs)?"


def is_qwen3next(facts) -> bool:
    return facts.get("config", {}).get("model_type") == "qwen3_next"


def moe_counter(facts, name: str, traced: bool = False) -> Optional[float]:
    """A counter of the expert layers over the untraced part of the window
    (`traced`: over the traced steps), from every step's own outputs."""
    counters = facts.get("counters") or {}
    return (counters.get("moe_traced" if traced else "moe") or {}).get(name)


def share_of_step_pct(facts, pattern: str) -> Optional[float]:
    trace = facts.get("trace")
    if not trace:
        return None
    _, step_s = xplane.module_matching(trace, STEP_MODULE)
    _, kernel_s = xplane.ops_matching(trace, kernel_label(pattern))
    return 100.0 * kernel_s / step_s if step_s and kernel_s else None


def _roofline_pct(facts, need, calls: float, seconds: float) -> float:
    floor = peaks.roofline_floor_s(
        need["flops"], need["bytes"],
        peaks.peaks_for(facts["device"]["kind"]))
    return 100.0 * floor["floor_s"] * calls / seconds


def _kernel_roofline_pct(facts, kernel: str, required) -> Optional[float]:
    """One kernel, found by its name: `required(cfg, batch, seq)[kernel]`
    a call over its device time a call."""
    trace = facts.get("trace")
    if not trace or not is_qwen3next(facts):
        return None
    calls, seconds = xplane.ops_matching(trace, kernel_label(kernel))
    if not calls or not seconds:
        return None
    cfg = facts["config"]
    need = required(cfg, int(cfg["train"]["per_chip_batch"]),
                    int(facts["traffic"]["seq"]))[kernel]
    return _roofline_pct(facts, need, calls, seconds)


def gdn_roofline_pct(facts, kernel: str) -> Optional[float]:
    return _kernel_roofline_pct(
        facts, kernel, lambda cfg, batch, seq: peaks_qwen3next.gdn_required(
            batch, seq, int(cfg["linear_num_key_heads"]),
            int(cfg["linear_num_value_heads"]),
            int(cfg["linear_value_head_dim"])))


def flash_d256_roofline_pct(facts, kernel: str) -> Optional[float]:
    """`peaks.flash_required` as it stands at this cell's [b, heads, seq,
    head_dim], over the kernel's time in this cell."""
    return _kernel_roofline_pct(
        facts, kernel, lambda cfg, batch, seq: peaks.flash_required(
            batch, int(cfg["num_attention_heads"]), seq,
            int(cfg["head_dim"])))


def moe_gmm_roofline_pct(facts) -> Optional[float]:
    """The traced steps' required work, by the assignments those steps
    made, over the grouped kernels' time in them."""
    trace = facts.get("trace")
    assigned = moe_counter(facts, "assigned_per_step", traced=True)
    if not trace or not is_qwen3next(facts) or assigned is None:
        return None
    steps, _ = xplane.module_matching(trace, STEP_MODULE)
    _, seconds = xplane.ops_matching(trace, kernel_label(MOE_KERNELS))
    if not steps or not seconds:
        return None
    cfg = facts["config"]
    need = peaks_qwen3next.moe_gmm_required(
        float(assigned), int(cfg["num_hidden_layers"]),
        int(cfg["num_experts"]), int(cfg["hidden_size"]),
        int(cfg["moe_intermediate_size"]))
    return _roofline_pct(facts, need, steps, seconds)
