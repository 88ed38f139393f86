"""Median admitted_at - submitted_at of the window's requests."""


def read(facts):
    return (facts.get("client") or {}).get("queue_ms")
