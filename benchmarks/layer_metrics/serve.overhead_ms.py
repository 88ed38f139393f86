"""Median over the window's requests of client TTFT (from the send) minus
the engine's own first_token_at - submitted_at: proxy, router, replica,
stream relay."""


def read(facts):
    return (facts.get("client") or {}).get("overhead_ms")
