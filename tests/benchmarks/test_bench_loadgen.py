"""The traffic generator: schedules from the seed, due-time accounting,
lateness, and the client against a local streaming server."""

import asyncio
import json
import threading
import time

import pytest

import _paths  # noqa: F401
from benchmarks import loadgen

CHAT = {"loop": "open", "rate_rps": 2.0, "shape_seed": 7, "lead_s": 3.0,
        "drain_s": 5.0,
        "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.8,
                   "min": 8, "max": 200},
        "output": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                   "min": 2, "max": 40}}
CLOSED = {"loop": "closed", "clients": 4, "pool": 50, "shape_seed": 9,
          "prompt": {"dist": "uniform", "min": 8, "max": 16},
          "output": {"dist": "uniform", "min": 2, "max": 6}}


def shapes(schedule, in_window=True):
    return sorted((r["prompt_len"], r["max_new_tokens"]) for r in schedule
                  if r.get("in_window", True) is in_window)


def test_same_seed_same_schedule():
    a = loadgen.open_schedule(CHAT, 3000000019, 20.0, 512)
    b = loadgen.open_schedule(CHAT, 3000000019, 20.0, 512)
    assert a == b


def test_another_seed_keeps_a_fixed_order_and_draws_other_tokens():
    a = loadgen.open_schedule(CHAT, 1, 20.0, 512)
    b = loadgen.open_schedule(CHAT, 2, 20.0, 512)
    assert [r["ids"] for r in a] != [r["ids"] for r in b]
    strip = lambda s: [{k: v for k, v in r.items() if k != "ids"} for r in s]
    assert strip(a) == strip(b)


def test_another_seed_reorders_the_same_work_where_the_mix_rotates():
    rotated = {**CHAT, "order": "rotated"}
    a = loadgen.open_schedule(rotated, 1, 20.0, 512)
    b = loadgen.open_schedule(rotated, 2, 20.0, 512)
    assert [r["ids"] for r in a] != [r["ids"] for r in b]
    assert [r["prompt_len"] for r in a] != [r["prompt_len"] for r in b]
    # ... but the window holds the same shapes and the same gaps
    assert shapes(a) == shapes(b)
    gaps = lambda s: sorted(round(y["due_s"] - x["due_s"], 9) for x, y in zip(
        [r for r in s if r["in_window"]], [r for r in s if r["in_window"]][1:]))
    # one gap (the one that closes the cycle) differs with the rotation
    ga, gb = gaps(a), gaps(b)
    assert len(set(ga) ^ set(gb)) <= 4


@pytest.mark.parametrize("seed", [0, 1, 2 ** 31 + 11, 3000000019])
def test_open_schedule_window_and_lead_in(seed):
    sched = loadgen.open_schedule({**CHAT, "order": "rotated"}, seed, 20.0,
                                  512)
    window = [r for r in sched if r["in_window"]]
    lead = [r for r in sched if not r["in_window"]]
    assert len(window) == 40                      # rate x seconds, exactly
    assert window[0]["due_s"] == 0.0
    assert all(0.0 <= r["due_s"] < 20.0 for r in window)
    assert lead and all(-3.0 <= r["due_s"] < 0.0 for r in lead)
    assert [r["due_s"] for r in sched] == sorted(r["due_s"] for r in sched)
    assert all(len(r["ids"]) == r["prompt_len"] for r in sched)
    assert all(1 <= t < 512 for r in sched for t in r["ids"])
    assert all(8 <= r["prompt_len"] <= 200 and 2 <= r["max_new_tokens"] <= 40
               for r in sched)


def test_rate_override_is_for_the_sweep():
    assert len([r for r in loadgen.open_schedule(CHAT, 1, 10.0, 512, rate=5.0)
                if r["in_window"]]) == 50


def test_closed_pool_rotates_by_seed():
    a = loadgen.closed_pool(CLOSED, 1, 512)
    b = loadgen.closed_pool(CLOSED, 2, 512)
    assert loadgen.closed_pool(CLOSED, 1, 512) == a
    assert shapes(a) == shapes(b)
    assert [r["ids"] for r in a] != [r["ids"] for r in b]


@pytest.mark.parametrize("dist,spec", [
    ("uniform", {"dist": "uniform", "min": 64, "max": 256}),
    ("lognormal", {"dist": "lognormal", "median": 512, "sigma": 0.9,
                   "min": 64, "max": 3072}),
    ("fixed", {"dist": "fixed", "value": 100, "min": 1, "max": 200}),
])
def test_lengths_respect_their_clips(dist, spec):
    import numpy as np

    out = loadgen.draw_lengths(spec, 500, np.random.default_rng(0))
    assert all(spec["min"] <= n <= spec["max"] for n in out)
    if dist == "lognormal":
        assert 350 < sorted(out)[250] < 750


def test_unknown_distribution_is_refused():
    import numpy as np

    with pytest.raises(ValueError):
        loadgen.draw_lengths({"dist": "zipf", "min": 1, "max": 2}, 1,
                             np.random.default_rng(0))


@pytest.mark.parametrize("q,want", [(50, 5), (90, 9), (99, 10), (100, 10),
                                    (10, 1), (1, 1)])
def test_percentile_is_nearest_rank(q, want):
    assert loadgen.percentile(list(range(10, 0, -1)), q) == want


def record(idx, due, send, tokens_at, in_window=True, final=True, n_new=None):
    ids = [1, 2, 3]
    toks = list(range(100, 100 + len(tokens_at)))
    return {"idx": idx, "key": idx, "prompt_len": 3,
            "max_new_tokens": n_new or len(tokens_at), "in_window": in_window,
            "t_due": due, "t_send": send, "t_done": tokens_at[-1],
            "token_times": tokens_at, "tokens": toks,
            "final_ids": ids + toks if final else None,
            "error": None if final else "cut", "cut": False}


def test_ttft_counts_from_the_due_time_and_lateness_is_reported():
    t0 = 1000.0
    recs = [record(0, t0 + 1.0, t0 + 1.5, [t0 + 2.0, t0 + 2.1, t0 + 2.4]),
            record(1, t0 - 1.0, t0 - 1.0, [t0 - 0.5, t0 + 0.5],
                   in_window=False),
            record(2, t0 + 9.0, t0 + 9.0, [t0 + 10.5, t0 + 10.6])]
    out = loadgen.reduce_records(recs, t0, 10.0)
    # request 0: first token 1.0 s after it was DUE (0.5 s after the send)
    assert out["ttft_ms"] == pytest.approx([1000.0, 1500.0])
    assert out["ttft_from_send_ms"][0] == pytest.approx(500.0)
    assert out["late_ms"] == pytest.approx([500.0, 0.0])
    # tokens and gaps by ARRIVAL inside the window: request 1's second
    # token and its gap count, request 2's tokens (after the end) do not
    assert out["tokens_in_window"] == 4
    assert sorted(out["gaps_ms"]) == pytest.approx([100.0, 300.0, 1000.0])
    assert out["attempted"] == 3 and out["failed"] == 0
    assert out["lead_in_requests"] == 1


def test_failures_count_against_attempts_and_a_cut_stream_has_not_failed():
    t0 = 0.0
    bad = record(0, None, 1.0, [2.0], final=False)
    cut = record(1, None, 1.0, [2.0], final=False)
    cut["cut"], cut["error"] = True, None
    out = loadgen.reduce_records([bad, cut, record(2, None, 1.0, [2.0])],
                                 t0, 10.0)
    assert (out["attempted"], out["failed"], out["cut_at_window_end"]) == \
        (3, 1, 1)


def test_wrong_answers_are_named():
    good = record(0, None, 0.0, [1.0, 2.0])
    short = record(1, None, 0.0, [1.0, 2.0], n_new=3)
    other = record(2, None, 0.0, [1.0, 2.0])
    other["final_ids"] = [1, 2, 3, 7, 8]
    prompts = {0: [1, 2, 3], 1: [1, 2, 3], 2: [1, 2, 3]}
    bad = loadgen.wrong_answers([good, short, other], prompts)
    assert len(bad) == 2 and "request 1" in bad[0] and "request 2" in bad[1]


# ---- the client against a local server that streams like the proxy ----


@pytest.fixture()
def stream_server():
    from aiohttp import web

    state = {"stall_s": 0.0, "port": None, "loop": None, "runner": None}

    async def handle(request):
        body = await request.json()
        resp = web.StreamResponse()
        resp.enable_chunked_encoding()
        await resp.prepare(request)
        await asyncio.sleep(state["stall_s"])
        toks = [len(body["ids"]) + i for i in range(body["max_new_tokens"])]
        for t in toks:
            await resp.write((json.dumps({"token": t}) + "\n").encode())
            await asyncio.sleep(0.01)
        await resp.write((json.dumps(
            {"done": True, "ids": body["ids"] + toks}) + "\n").encode())
        await resp.write_eof()
        return resp

    ready = threading.Event()

    def serve():
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        app = web.Application()
        app.router.add_post("/", handle)
        runner = web.AppRunner(app)
        loop.run_until_complete(runner.setup())
        site = web.TCPSite(runner, "127.0.0.1", 0)
        loop.run_until_complete(site.start())
        state["port"] = site._server.sockets[0].getsockname()[1]
        state["loop"], state["runner"] = loop, runner
        ready.set()
        loop.run_forever()
        loop.run_until_complete(runner.cleanup())
        loop.close()

    thread = threading.Thread(target=serve, daemon=True)
    thread.start()
    assert ready.wait(10)
    yield state
    state["loop"].call_soon_threadsafe(state["loop"].stop)
    thread.join(timeout=10)
    assert not thread.is_alive()


def test_open_loop_client_times_from_due_and_checks_answers(stream_server):
    stream_server["stall_s"] = 0.2
    url = f"http://127.0.0.1:{stream_server['port']}/"
    sched = loadgen.open_schedule({**CHAT, "rate_rps": 10.0, "lead_s": 0.3},
                                  5, 1.0, 512)
    t_zero = time.monotonic() + 0.5
    recs = loadgen.run_open_loop(url, sched, t_zero, 1.0, 5.0)
    assert len(recs) == len(sched)
    assert loadgen.wrong_answers(recs, {r["idx"]: r["ids"] for r in sched}) \
        == []
    out = loadgen.reduce_records(recs, t_zero, 1.0)
    assert out["failed"] == 0 and out["attempted"] == len(sched)
    assert len(out["ttft_ms"]) == 10
    # the server stalls 200 ms before the first token
    assert all(190 < t < 600 for t in out["ttft_ms"])
    assert max(out["late_ms"]) < 100
    assert out["tokens_in_window"] > 0


def test_closed_loop_client_keeps_its_callers_busy(stream_server):
    url = f"http://127.0.0.1:{stream_server['port']}/"
    pool = loadgen.closed_pool(CLOSED, 3, 512)
    t_zero = time.monotonic() + 0.2
    recs = loadgen.run_closed_loop(url, pool, 4, t_zero, 0.8)
    out = loadgen.reduce_records(recs, t_zero, 0.8)
    done = [r for r in recs if r["final_ids"] is not None]
    assert len(done) >= 8 and out["failed"] == 0
    assert out["cut_at_window_end"] <= 4
    prompts = {r["idx"]: pool[r["idx"] % len(pool)]["ids"] for r in recs}
    assert loadgen.wrong_answers(recs, prompts) == []


def test_an_unreachable_server_fails_every_request():
    sched = loadgen.open_schedule({**CHAT, "lead_s": 0.0}, 5, 0.5, 512)
    t_zero = time.monotonic()
    recs = loadgen.run_open_loop("http://127.0.0.1:9/", sched, t_zero, 0.5,
                                 1.0)
    out = loadgen.reduce_records(recs, t_zero, 0.5)
    assert out["attempted"] == len(sched) == out["failed"]
