"""Of the window's prefill chunks, the share that rode in a decode step
(one execution, the weights read once for both) and did not run alone:
the engine's `steps["chunks_aboard"]` over that plus `steps["prefill"]`,
between the window's two reads of its ledger (the builder's
`window_steps`). Says whether the fused step is engaged; nothing where the
program's ledger has no such count."""


def read(facts):
    steps = (facts.get("counters") or {}).get("window_steps") or {}
    if "chunks_aboard" not in steps:
        return None
    chunks = steps["chunks_aboard"] + steps["prefill"]
    return 100.0 * steps["chunks_aboard"] / chunks if chunks else None
