"""90th percentile (nearest rank) of time to first token at the client, from
when the request was due. In a traced run: over the part of
the window before the profiler starts. No bound: PERF.md section 2 says why."""


def read(facts):
    return (facts.get("client") or {}).get("ttft_p90_ms")
