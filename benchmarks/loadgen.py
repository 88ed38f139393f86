"""One general traffic generator. A traffic mix is a data file under
`benchmarks/traffic/` (lengths, rate or client count, sharing); nothing
here knows a mix by name.

Steadiness by construction: the SET of request shapes and of arrival gaps
is drawn from the file's own `shape_seed`, so every run seed sees the same
work; `--seed` decides the token ids (and the weights, elsewhere) and,
where the file says `"order": "rotated"`, where in the cycle the run
starts. An open-loop schedule is one cycle of
exactly `round(rate * seconds)` arrivals whose gaps sum to the window, so
every window holds every shape once whatever the rotation; the requests
that precede the window in the cycle are sent during a lead-in so that the
window starts in the cycle's steady state, not on an empty engine.

Open loop times a request from when it was DUE, not from when it was sent,
and reports how late the generator ran (`bench.py _poisson_http_load`
timed from the send and reported no lateness: copied and repaired here).
"""

from __future__ import annotations

import asyncio
import itertools
import json
import math
import time
import zlib
from typing import Any, Dict, List, Optional

import numpy as np

# --------------------------------------------------------------------------- #
# shapes and schedules (pure; tested on the CPU)
# --------------------------------------------------------------------------- #


def draw_lengths(spec: Dict[str, Any], n: int, rng: np.random.Generator
                 ) -> List[int]:
    """n lengths from {"dist": "uniform"|"lognormal"|"fixed", ...}, clipped
    to [min, max]."""
    lo, hi = int(spec["min"]), int(spec["max"])
    dist = spec["dist"]
    if dist == "fixed":
        raw = np.full(n, float(spec["value"]))
    elif dist == "uniform":
        raw = rng.integers(lo, hi + 1, size=n).astype(float)
    elif dist == "lognormal":
        raw = np.exp(rng.normal(math.log(float(spec["median"])),
                                float(spec["sigma"]), size=n))
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return [int(x) for x in np.clip(np.rint(raw), lo, hi)]


def _token_ids(rng: np.random.Generator, n: int, vocab: int) -> List[int]:
    return [int(t) for t in rng.integers(1, vocab, size=n)]


def prompt_key(ids: List[int]) -> int:
    """What a client request and the engine's record of it are matched by."""
    return zlib.crc32(np.asarray(ids, np.int32).tobytes())


def open_schedule(traffic: Dict[str, Any], seed: int, seconds: float,
                  vocab: int, rate: Optional[float] = None
                  ) -> List[Dict[str, Any]]:
    """The requests of an open-loop run, sorted by due time. `due_s` is
    relative to the start of the measured window; lead-in requests have
    negative due times. `in_window` marks the requests the tails are
    taken over."""
    rate = float(traffic["rate_rps"] if rate is None else rate)
    n = max(1, int(round(rate * seconds)))
    shape_rng = np.random.default_rng(int(traffic["shape_seed"]))
    gaps = shape_rng.exponential(1.0, size=n)
    gaps *= seconds / gaps.sum()
    offsets = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])   # c_0 = 0
    prompts = draw_lengths(traffic["prompt"], n, shape_rng)
    outputs = draw_lengths(traffic["output"], n, shape_rng)
    rng = np.random.default_rng(int(seed))
    k = int(rng.integers(n))
    if traffic.get("order", "fixed") != "rotated":
        # A mix with few requests to a window keeps its order: a tail
        # there is ONE request's time, and where in the engine's step
        # train an arrival lands moves it by a step. The seed then draws
        # the token ids (and the weights) only.
        k = 0
    lead_s = float(traffic.get("lead_s", 0.0))
    picks = []          # (due_s, cycle index, in_window)
    for m in range(n):
        i = (k + m) % n
        picks.append((float((offsets[i] - offsets[k]) % seconds), i, True))
    m, due = 1, 0.0
    while lead_s > 0:
        i = (k - m) % n
        due -= float(gaps[i])
        if due < -lead_s:
            break
        picks.append((due, i, False))
        m += 1
    picks.sort()
    return [{"idx": j, "due_s": due, "in_window": inw,
             "prompt_len": prompts[i], "max_new_tokens": outputs[i],
             "ids": _token_ids(rng, prompts[i], vocab)}
            for j, (due, i, inw) in enumerate(picks)]


def closed_pool(traffic: Dict[str, Any], seed: int, vocab: int
                ) -> List[Dict[str, Any]]:
    """The requests of a closed-loop run in the order clients take them:
    the file's pool of shapes, rotated by the seed."""
    n = int(traffic["pool"])
    shape_rng = np.random.default_rng(int(traffic["shape_seed"]))
    prompts = draw_lengths(traffic["prompt"], n, shape_rng)
    outputs = draw_lengths(traffic["output"], n, shape_rng)
    rng = np.random.default_rng(int(seed))
    k = int(rng.integers(n))
    order = [(k + m) % n for m in range(n)]
    return [{"idx": j, "prompt_len": prompts[i], "max_new_tokens": outputs[i],
             "ids": _token_ids(rng, prompts[i], vocab)}
            for j, i in enumerate(order)]


def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile (q in 0..100) of a non-empty list: the
    value itself of a real request, never an interpolation."""
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


def histogram(values: List[float], width: float) -> Dict[str, int]:
    """Counts by bins of `width` (keyed by the bin's lower edge), for the
    lines that say how a tail came about."""
    out: Dict[str, int] = {}
    for v in values:
        key = f"{int(v // width * width)}"
        out[key] = out.get(key, 0) + 1
    return dict(sorted(out.items(), key=lambda kv: int(kv[0])))


# --------------------------------------------------------------------------- #
# the client: one process, one thread, one asyncio loop
# --------------------------------------------------------------------------- #


async def _stream_one(session, url: str, req: Dict[str, Any],
                      rec: Dict[str, Any]) -> None:
    """POST one streamed request; stamp every token as its line arrives."""
    import aiohttp

    rec["t_send"] = time.monotonic()
    try:
        async with session.post(url, json={
                "ids": req["ids"], "max_new_tokens": req["max_new_tokens"],
                "stream": True}) as resp:
            if resp.status != 200:
                rec["error"] = f"http {resp.status}: " \
                    f"{(await resp.text())[:200]}"
                return
            async for raw in resp.content:
                now = time.monotonic()
                line = raw.strip()
                if not line:
                    continue
                event = json.loads(line)
                if "token" in event:
                    rec["token_times"].append(now)
                    rec["tokens"].append(int(event["token"]))
                elif event.get("done"):
                    rec["final_ids"] = event["ids"]
                    rec["t_done"] = now
                elif "error" in event:
                    rec["error"] = str(event["error"])[:200]
        if rec["final_ids"] is None and rec["error"] is None:
            rec["error"] = "stream ended without a final line"
    except (aiohttp.ClientError, asyncio.TimeoutError, ValueError,
            ConnectionError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"[:200]


def _new_record(req: Dict[str, Any], due: Optional[float]) -> Dict[str, Any]:
    return {"idx": req["idx"], "key": prompt_key(req["ids"]),
            "prompt_len": req["prompt_len"],
            "max_new_tokens": req["max_new_tokens"],
            "in_window": req.get("in_window", True),
            "t_due": due, "t_send": None, "t_done": None,
            "token_times": [], "tokens": [], "final_ids": None,
            "error": None, "cut": False}


async def _run_open(url, schedule, t_zero, seconds, drain_s):
    import aiohttp

    records, tasks = [], []
    timeout = aiohttp.ClientTimeout(total=None, sock_read=drain_s + seconds)
    conn = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(timeout=timeout, connector=conn) as s:
        for req in schedule:
            due = t_zero + req["due_s"]
            delay = due - time.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
            rec = _new_record(req, due)
            records.append(rec)
            tasks.append(asyncio.ensure_future(_stream_one(s, url, req, rec)))
        # Bounded drain: what has not finished by then has failed.
        deadline = t_zero + seconds + drain_s
        pending = set(tasks)
        while pending and time.monotonic() < deadline:
            _, pending = await asyncio.wait(
                pending, timeout=max(0.0, deadline - time.monotonic()))
        for task in pending:
            task.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
    for rec in records:
        if rec["final_ids"] is None and rec["error"] is None:
            rec["error"] = f"not finished {drain_s:.0f} s after the window"
    return records


async def _run_closed(url, pool, clients, t_zero, seconds):
    import aiohttp

    records: List[Dict[str, Any]] = []
    taken = itertools.count()
    t_end = t_zero + seconds
    timeout = aiohttp.ClientTimeout(total=None, sock_read=seconds + 120)
    conn = aiohttp.TCPConnector(limit=0)

    async def client(session):
        while time.monotonic() < t_end:
            # Past its end the pool wraps (prompts repeat and would hit
            # the prefix cache): the caller treats that as a fault.
            n = next(taken)
            req = {**pool[n % len(pool)], "idx": n}
            rec = _new_record(req, None)
            records.append(rec)
            await _stream_one(session, url, req, rec)

    async with aiohttp.ClientSession(timeout=timeout, connector=conn) as s:
        tasks = [asyncio.ensure_future(client(s)) for _ in range(clients)]
        await asyncio.sleep(max(0.0, t_end - time.monotonic()))
        # The window is over: what is still in flight is cut, not failed
        # (the disconnect makes the engine cancel it and free its blocks).
        for rec in records:
            if rec["final_ids"] is None and rec["error"] is None:
                rec["cut"] = True
        for task in tasks:
            task.cancel()
        done = await asyncio.gather(*tasks, return_exceptions=True)
    for out in done:
        if isinstance(out, Exception) and not isinstance(
                out, asyncio.CancelledError):
            raise out
    return records


def run_open_loop(url: str, schedule: List[Dict[str, Any]], t_zero: float,
                  seconds: float, drain_s: float) -> List[Dict[str, Any]]:
    """Send `schedule` (due times relative to `t_zero`, a
    `time.monotonic()` value in the near future); returns one record per
    request with client-side stamps."""
    return asyncio.run(_run_open(url, schedule, t_zero, seconds, drain_s))


def run_closed_loop(url: str, pool: List[Dict[str, Any]], clients: int,
                    t_zero: float, seconds: float) -> List[Dict[str, Any]]:
    """`clients` callers, each sending its next request when the last
    returned, from now until `t_zero + seconds`."""
    return asyncio.run(_run_closed(url, pool, clients, t_zero, seconds))


# --------------------------------------------------------------------------- #
# reduction of client records to numbers
# --------------------------------------------------------------------------- #


def reduce_records(records: List[Dict[str, Any]], t_zero: float,
                   seconds: float) -> Dict[str, Any]:
    """Client-side facts of a run. Token gaps and the token count are
    taken over tokens that ARRIVED inside the window; TTFT (from the due
    time where there is one, else from the send) over requests due, or
    sent, inside it."""
    t_end = t_zero + seconds
    tokens_in_window = 0
    gaps_ms: List[float] = []
    ttft_due_ms: List[float] = []
    ttft_send_ms: Dict[int, float] = {}
    late_ms: List[float] = []
    for rec in records:
        times = rec["token_times"]
        tokens_in_window += sum(1 for t in times if t_zero <= t < t_end)
        gaps_ms += [(b - a) * 1e3 for a, b in zip(times, times[1:])
                    if t_zero <= b < t_end]
        start = rec["t_due"] if rec["t_due"] is not None else rec["t_send"]
        if start is None or not (t_zero <= start < t_end) \
                or not rec["in_window"]:
            continue
        if rec["t_due"] is not None and rec["t_send"] is not None:
            late_ms.append((rec["t_send"] - rec["t_due"]) * 1e3)
        if times:
            ttft_due_ms.append((times[0] - start) * 1e3)
            ttft_send_ms[rec["key"]] = (times[0] - rec["t_send"]) * 1e3
    # Whether a backlog grows: requests still open when the window ends,
    # and TTFT of the window's first half against its second.
    halves: List[List[float]] = [[], []]
    for rec in records:
        start = rec["t_due"] if rec["t_due"] is not None else rec["t_send"]
        if rec["token_times"] and start is not None \
                and t_zero <= start < t_end:
            halves[int(start >= t_zero + seconds / 2)].append(
                (rec["token_times"][0] - start) * 1e3)
    return {
        "open_at_window_end": sum(
            1 for r in records if r["t_send"] is not None
            and r["t_send"] < t_end
            and (r["t_done"] is None or r["t_done"] >= t_end)),
        "ttft_half_median_ms": [
            sorted(h)[len(h) // 2] if h else None for h in halves],
        "tokens_in_window": tokens_in_window,
        "gaps_ms": gaps_ms, "ttft_ms": ttft_due_ms,
        "ttft_from_send_ms": ttft_send_ms,
        "late_ms": late_ms,
        # A stream cut when the window closed was attempted and had not
        # failed: it counts, with an error only if it had one.
        "attempted": len(records),
        "failed": sum(1 for r in records if r["error"] is not None),
        "cut_at_window_end": sum(1 for r in records if r["cut"]),
        "lead_in_requests": sum(1 for r in records if not r["in_window"]),
    }


def wrong_answers(records: List[Dict[str, Any]],
                  prompts: Dict[int, List[int]]) -> List[str]:
    """Every finished response holds its prompt plus exactly the tokens
    asked for, and the streamed tokens are the final ids' tail."""
    bad = []
    for rec in records:
        if rec["final_ids"] is None:
            continue
        prompt = prompts[rec["idx"]]
        want = len(prompt) + rec["max_new_tokens"]
        if len(rec["final_ids"]) != want:
            bad.append(f"request {rec['idx']}: {len(rec['final_ids'])} ids, "
                       f"want {want}")
        elif rec["final_ids"][:len(prompt)] != prompt:
            bad.append(f"request {rec['idx']}: prompt not echoed")
        elif rec["final_ids"][len(prompt):] != rec["tokens"]:
            bad.append(f"request {rec['idx']}: streamed tokens differ from "
                       f"the final ids")
    return bad
