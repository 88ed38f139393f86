"""What the Ouro cell's readers share. Each returns None where the
configuration is not an `ouro` one, or the program has no such kernel or
counter (the parent commit has neither)."""

from __future__ import annotations

from typing import Optional

from benchmarks import peaks, peaks_ouro, xplane
from benchmarks.layer_metrics._common import (DECODE_MODULE, PREFILL_MODULE,
                                              kernel_label)

PAGED_KERNEL = "paged_attention"


def is_ouro(facts) -> bool:
    return facts.get("config", {}).get("model_type") == "ouro"


def _peaks(facts):
    """The chip's peaks, or None off the chip: a utilisation of the
    chip's peak, or nothing."""
    if facts["device"]["platform"] != "tpu":
        return None
    return peaks.peaks_for(facts["device"]["kind"])


def _rates(facts):
    """The untraced part of the window, client side: decoded tokens/s
    (`out`), prompt tokens/s (`pre`), `requests`/s, mean `context`, mean
    `prompt`, and live `rows` a decode step; None where any is missing."""
    client = facts.get("client") or {}
    got = {"out": client.get("out_tok_s"), "pre": client.get("prefill_tok_s"),
           "requests": client.get("requests_s"),
           "context": client.get("mean_context"),
           "prompt": client.get("mean_prompt"),
           "rows": (facts.get("counters") or {}).get("rows_per_decode_step")}
    return None if any(v is None for v in got.values()) or not got["rows"] \
        else got


def serve_mfu_pct(facts) -> Optional[float]:
    """Tokens a second through decode (at the window's mean context) and
    through prefill (causal: half the mean prompt) times a token's model
    FLOPs, every pass counted, over the chip's bf16 peak."""
    if not is_ouro(facts):
        return None
    rates, peak = _rates(facts), _peaks(facts)
    if rates is None or peak is None:
        return None
    cfg = facts["config"]
    flops = rates["out"] * peaks_ouro.serve_flops_per_token(
        cfg, rates["context"], True) \
        + rates["pre"] * peaks_ouro.serve_flops_per_token(
            cfg, rates["prompt"] / 2.0, False)
    return 100.0 * flops / peak["flops_per_s"]


def serve_membw_pct(facts) -> Optional[float]:
    """The executions' required bytes a second over the chip's HBM
    bandwidth (`peaks_ouro.step_bytes`). Decode executions a second: the
    decoded tokens that are not a request's first (its prefill chunk gives
    that) over the live rows of a step; each reads the weights of every
    pass, the head, its rows' cached tokens, and writes a token a row.
    Prefill executions a second: one a request; each reads the same
    weights, and reads and writes its prompt's keys and values once."""
    if not is_ouro(facts):
        return None
    rates, peak = _rates(facts), _peaks(facts)
    if rates is None or peak is None:
        return None
    cfg, rows, prompt = facts["config"], rates["rows"], rates["prompt"]
    per_s = (rates["out"] - rates["requests"]) / rows \
        * peaks_ouro.step_bytes(cfg, rows, rows * rates["context"]) \
        + rates["requests"] * peaks_ouro.step_bytes(cfg, prompt, prompt)
    return 100.0 * per_s / peak["hbm_bytes_per_s"]


def _decode_calls(facts):
    """(executions, seconds) of the paged kernel's DECODE calls in the
    trace: both programs call one kernel under one name, and a decode
    call's result is [slots, kv_heads, ...] where a chunk's is [1, ...]."""
    cfg = facts["config"]
    slots = int(cfg["engine"]["batch_slots"])
    return xplane.ops_matching(
        facts["trace"], kernel_label(PAGED_KERNEL)
        + rf".*\[{slots},{int(cfg['num_key_value_heads'])},")


def paged_share_pct(facts) -> Optional[float]:
    """Device time of the `paged_attention` kernel over that of the
    engine's two programs."""
    trace = facts.get("trace")
    if not trace or not is_ouro(facts):
        return None
    _, decode_s = xplane.module_matching(trace, DECODE_MODULE)
    _, prefill_s = xplane.module_matching(trace, PREFILL_MODULE)
    _, kernel_s = xplane.ops_matching(trace, kernel_label(PAGED_KERNEL))
    total = decode_s + prefill_s
    return 100.0 * kernel_s / total if total and kernel_s else None


def paged_decode_roofline_pct(facts) -> Optional[float]:
    """The traced decode calls' live keys and values (and q, o) over peak
    bandwidth, or their FLOPs over peak, whichever is larger, over their
    device time: one query row a KV head, 16 KV heads. Rows and cached
    tokens a call are the traced interval's own: the tokens the clients
    were handed inside it and what each attended over, spread over the
    interval's steps (a call a layer a pass a step)."""
    trace = facts.get("trace")
    if not trace or not is_ouro(facts):
        return None
    cfg, client = facts["config"], facts.get("client") or {}
    calls, seconds = _decode_calls(facts)
    decoded = client.get("traced_decoded")
    if not calls or not seconds or not decoded:
        return None
    steps = calls / (int(cfg["total_ut_steps"])
                     * int(cfg["num_hidden_layers"]))
    need = peaks_ouro.paged_decode_required(
        cfg, decoded / steps, client["traced_context_sum"] / steps)
    floor = peaks.roofline_floor_s(
        need["flops"], need["bytes"],
        peaks.peaks_for(facts["device"]["kind"]))
    return 100.0 * floor["floor_s"] * calls / seconds


def passes_per_token(facts) -> Optional[float]:
    """Passes executed over live tokens since the warm-up, from the
    counters the step keeps in its cache."""
    loop = (facts.get("counters") or {}).get("loop") or {}
    if not is_ouro(facts) or not loop.get("tokens"):
        return None
    return loop["passes"] / loop["tokens"]


def arena_fill_pct(facts) -> Optional[float]:
    """The blocks the live rows of a decode step held, a step since the
    warm-up, over those the manager may hand out (every block but the
    trash block): the share of the arena at work, from the counters the
    step keeps in its cache. (Blocks the radix cache keeps for finished
    requests are not counted: with them the arena is always full.)"""
    counters = facts.get("counters") or {}
    loop, kv = counters.get("loop") or {}, counters.get("kv") or {}
    if not is_ouro(facts) or not loop.get("decode_steps") \
            or not kv.get("num_blocks"):
        return None
    return 100.0 * loop["decode_blocks"] / loop["decode_steps"] \
        / (kv["num_blocks"] - 1)
