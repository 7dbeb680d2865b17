"""Eqn-1 score calls per Prompt Bank lookup in the window: the calls the
benchmark's wrapper of the service's score function counted, over the
lookups completed (the two-layer lookup makes K + |cluster| - 1)."""


def read(run):
    c = run["counters"]
    if not c.get("lookups"):
        return None
    return c["score_calls"] / c["lookups"]
