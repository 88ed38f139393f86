"""What the SDAR cell's readers share. Each returns None where the
configuration is not an `sdar_moe` one, or the program has no such kernel
or counter (the parent commit has neither)."""

from __future__ import annotations

from typing import Optional

from benchmarks import peaks, peaks_sdar, xplane
from benchmarks.layer_metrics._common import (DECODE_MODULE, PREFILL_MODULE,
                                              kernel_label)

PAGED_KERNEL = "paged_attention"
MOE_KERNEL = "moe_gmm"


def is_sdar(facts) -> bool:
    return facts.get("config", {}).get("model_type") == "sdar_moe"


def _peaks(facts):
    """The chip's peaks, or None off the chip: a utilisation of the
    chip's peak, or nothing."""
    if facts["device"]["platform"] != "tpu":
        return None
    return peaks.peaks_for(facts["device"]["kind"])


def _book(facts) -> Optional[dict]:
    """`stats()["diffusion"]` since the warm-up, where any row-pass ran."""
    book = (facts.get("counters") or {}).get("diffusion") or {}
    passes = book.get("denoise_passes", 0) + book.get("commit_passes", 0)
    return {**book, "passes": passes} if is_sdar(facts) and passes else None


def _moe(facts, kind: str) -> Optional[dict]:
    moe = (facts.get("counters") or {}).get("moe") or {}
    got = moe.get(kind)
    return got if is_sdar(facts) and got and got.get("steps") else None


def passes_per_token(facts) -> Optional[float]:
    """Row-passes (a row's block through one execution) over the positions
    the passes committed: 5 / 4 under the cell's schedule, less where a
    block opened with given tokens."""
    book = _book(facts)
    if not book or not book.get("tokens_committed"):
        return None
    return book["passes"] / book["tokens_committed"]


def commit_share_pct(facts) -> Optional[float]:
    """Commit row-passes over all row-passes: the passes that choose
    nothing and only make a block's keys and values final."""
    book = _book(facts)
    return 100.0 * book["commit_passes"] / book["passes"] if book else None


def experts_drawn_per_step(facts) -> Optional[float]:
    """Experts that drew a row, a layer a block step."""
    moe = _moe(facts, "decode")
    return moe["experts_drawn_per_step"] if moe else None


def _rates(facts):
    """The untraced part of the window, client side: emitted tokens/s
    (`out`), prompt tokens/s (`pre`), `requests`/s, mean `context` of an
    emitted token, mean `prompt`, live `rows` a block execution, and the
    `passes` a committed position took."""
    client = facts.get("client") or {}
    got = {"out": client.get("out_tok_s"), "pre": client.get("prefill_tok_s"),
           "requests": client.get("requests_s"),
           "context": client.get("mean_context"),
           "prompt": client.get("mean_prompt"),
           "rows": (facts.get("counters") or {}).get("rows_per_decode_step"),
           "passes": passes_per_token(facts)}
    return None if any(v is None for v in got.values()) or not got["rows"] \
        else got


def _seen(cfg, context: float) -> float:
    """What a position sees whose block holds an emitted token with
    `context` tokens before it: them and, on average over the block's
    positions, (L + 1) / 2 of the block."""
    return context + (int(cfg["block_length"]) + 1) / 2.0


def serve_mfu_pct(facts) -> Optional[float]:
    """The share of the chip's bf16 peak that the whole window, prefills
    and block executions, puts to the model's required arithmetic
    (`peaks_sdar`): every row-pass of the schedule counted, so FIVE passes
    of a position a committed token under the cell's schedule (`passes` x
    `block_length`), each with the head; a prompt token once, without."""
    if not is_sdar(facts):
        return None
    rates, peak = _rates(facts), _peaks(facts)
    if rates is None or peak is None:
        return None
    cfg = facts["config"]
    per_token = rates["passes"] * int(cfg["block_length"])
    flops = rates["out"] * per_token * peaks_sdar.flops_per_row_pass(
        cfg, _seen(cfg, rates["context"]), True) \
        + rates["pre"] * peaks_sdar.flops_per_row_pass(
            cfg, rates["prompt"] / 2.0, False)
    return 100.0 * flops / peak["flops_per_s"]


def serve_membw_pct(facts) -> Optional[float]:
    """The executions' required bytes a second over the chip's HBM
    bandwidth (`peaks_sdar.execution_bytes`). Block executions a second:
    the emitted tokens times the passes a committed position took, over a
    block's positions and the live rows of an execution; each reads the
    attention and router weights, the experts that drew a row (by the
    step's counters), the head, its rows' visible keys and values, and
    writes a block a row. Prefill executions a second: one a request."""
    if not is_sdar(facts):
        return None
    rates, peak = _rates(facts), _peaks(facts)
    block, chunk = _moe(facts, "decode"), _moe(facts, "prefill")
    if rates is None or peak is None or not block or not chunk:
        return None
    cfg, rows, prompt = facts["config"], rates["rows"], rates["prompt"]
    length = int(cfg["block_length"])
    per_s = rates["out"] * rates["passes"] / rows \
        * peaks_sdar.execution_bytes(
            cfg, rows * length, rows * _seen(cfg, rates["context"]),
            block["experts_drawn_per_step"], True) \
        + rates["requests"] * peaks_sdar.execution_bytes(
            cfg, prompt, prompt, chunk["experts_drawn_per_step"], False)
    return 100.0 * per_s / peak["hbm_bytes_per_s"]


def _programs_s(trace) -> float:
    _, decode_s = xplane.module_matching(trace, DECODE_MODULE)
    _, prefill_s = xplane.module_matching(trace, PREFILL_MODULE)
    return decode_s + prefill_s


def share_pct(facts, kernel: str) -> Optional[float]:
    """Device time of `kernel` over that of the engine's two programs."""
    trace = facts.get("trace")
    if not trace or not is_sdar(facts):
        return None
    total = _programs_s(trace)
    _, kernel_s = xplane.ops_matching(trace, kernel_label(kernel))
    return 100.0 * kernel_s / total if total and kernel_s else None


def _block_calls(facts):
    """(executions, seconds) of the paged kernel's calls in BLOCK steps:
    both programs call one kernel under one name, and a block step's
    result is [slots, kv_heads, ...] where a chunk's is [1, ...]."""
    cfg = facts["config"]
    slots = int(cfg["engine"]["batch_slots"])
    return xplane.ops_matching(
        facts["trace"], kernel_label(PAGED_KERNEL)
        + rf".*\[{slots},{int(cfg['num_key_value_heads'])},")


def paged_block_roofline_pct(facts) -> Optional[float]:
    """The traced block steps' calls: each live row's visible keys and
    values ONCE for its `block_length` queries (and q, o) over peak
    bandwidth, or their FLOPs over peak, whichever is larger, over their
    device time: 32 query rows a KV head, 4 KV heads. Rows a call are the
    window's (the engine's ledger); what a row sees is the traced
    interval's own: the mean context of the tokens the clients were handed
    inside it."""
    trace = facts.get("trace")
    if not trace or not is_sdar(facts):
        return None
    cfg, client = facts["config"], facts.get("client") or {}
    calls, seconds = _block_calls(facts)
    decoded = client.get("traced_decoded")
    rows = (facts.get("counters") or {}).get("rows_per_decode_step")
    if not calls or not seconds or not decoded or not rows:
        return None
    length = int(cfg["block_length"])
    seen = _seen(cfg, client["traced_context_sum"] / decoded)
    need = peaks_sdar.paged_required(
        cfg, rows * length, rows * seen, rows * length * seen)
    floor = peaks.roofline_floor_s(
        need["flops"], need["bytes"],
        peaks.peaks_for(facts["device"]["kind"]))
    return 100.0 * floor["floor_s"] * calls / seconds


def moe_gmm_roofline_pct(facts) -> Optional[float]:
    """The grouped products' required work in the traced executions (the
    window's own counts a kind: assignments and experts that drew a row an
    execution a layer), over their device time."""
    trace = facts.get("trace")
    if not trace or not is_sdar(facts):
        return None
    _, seconds = xplane.ops_matching(trace, kernel_label(MOE_KERNEL))
    dec, _ = xplane.module_matching(trace, DECODE_MODULE)
    pre, _ = xplane.module_matching(trace, PREFILL_MODULE)
    if not seconds or not (dec or pre):
        return None
    cfg = facts["config"]
    layers = int(cfg["num_hidden_layers"])
    flops = nbytes = 0.0
    for runs, kind in ((dec, "decode"), (pre, "prefill")):
        moe = _moe(facts, kind)
        if not runs:
            continue
        if not moe:
            return None
        need = peaks_sdar.moe_gmm_required(
            cfg, moe["assignments_per_step"], moe["experts_drawn_per_step"])
        # (a prefill's last layer runs no expert products: nothing reads
        # them, `peaks_sdar`'s docstring)
        ran = layers - (kind == "prefill")
        flops += runs * ran * need["flops"]
        nbytes += runs * ran * need["bytes"]
    floor = peaks.roofline_floor_s(
        flops, nbytes, peaks.peaks_for(facts["device"]["kind"]))
    return 100.0 * floor["floor_s"] / seconds
