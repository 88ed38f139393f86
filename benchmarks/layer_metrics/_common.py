"""What the per-layer readers share. A reader takes `facts` (the spans,
counters, client-side reductions and trace digest of one run, plus the
cell's files) and returns a number, or None when there is nothing to
read."""

from __future__ import annotations

from typing import Any, Dict, Optional

from benchmarks import peaks, xplane

STEP_MODULE = r"^jit_step_with_rules$"
DECODE_MODULE = r"^jit_decode_fn$"
PREFILL_MODULE = r"^jit_prefill_fn$"
FLASH_KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def span(facts: Dict[str, Any], name: str) -> Optional[float]:
    return (facts.get("spans") or {}).get(name)


def span_between(facts, start: str, end: str) -> Optional[float]:
    a, b = span(facts, start), span(facts, end)
    return None if a is None or b is None else b - a


def idle_pct(facts) -> Optional[float]:
    trace = facts.get("trace")
    if not trace or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def module_step_ms(facts, pattern: str) -> Optional[float]:
    """Device time per execution of an XLA module, in ms."""
    trace = facts.get("trace")
    if not trace:
        return None
    n, seconds = xplane.module_matching(trace, pattern)
    return seconds / n * 1e3 if n else None


def kernel_label(kernel: str) -> str:
    """A flash kernel is found by its stable name, in the op's HLO name or
    its name scope; `flash_bwd_dq` must not also match `flash_bwd_dkv`."""
    return rf"(^|[/%\s|]){kernel}(\.\d+)?($|[/\s|])"


def flash_roofline_pct(facts, kernel: str) -> Optional[float]:
    """Required FLOPs over peak, or required bytes over peak bandwidth,
    whichever is larger, over the kernel's device time."""
    trace = facts.get("trace")
    if not trace:
        return None
    calls, seconds = xplane.ops_matching(trace, kernel_label(kernel))
    if not calls or not seconds:
        return None
    cfg, traffic = facts["config"], facts["traffic"]
    need = peaks.flash_required(
        int(cfg["train"]["per_chip_batch"]), int(cfg["n_head"]),
        int(traffic["seq"]), int(cfg["n_embd"]) // int(cfg["n_head"]))[kernel]
    floor = peaks.roofline_floor_s(
        need["flops"], need["bytes"],
        peaks.peaks_for(facts["device"]["kind"]))
    return 100.0 * floor["floor_s"] * calls / seconds
