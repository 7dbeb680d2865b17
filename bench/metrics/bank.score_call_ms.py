"""Host milliseconds per Eqn-1 score call in the window, from the
benchmark's wrapper around each call; every call ends in a host sync
(``float(loss)``), so this is the call's whole latency."""


def read(run):
    c = run["counters"]
    if not c.get("score_calls"):
        return None
    return 1e3 * c["score_call_s"] / c["score_calls"]
