"""99th percentile of the gap between consecutive streamed tokens at the
client, pooled over the requests. In a traced run: over the part of
the window before the profiler starts. No bound: PERF.md section 2 says why."""


def read(facts):
    return (facts.get("client") or {}).get("itl_p99_ms")
