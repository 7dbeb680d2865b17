"""Trace, lowering and compile-or-load milliseconds per Prompt Bank
lookup, from the program's ``jit.*`` counters under its
``service.submit`` spans over its ``bank.lookup`` spans: what the fresh
score program of each routed job costs."""


def read(run):
    try:
        from repro.obs import device
    except ImportError:              # a program without device spans
        return None
    snap = device.snapshot()
    lookups = device.total(snap["spans"], "count", span="bank.lookup")
    if not lookups:
        return None
    return 1e3 * device.jit_seconds(snap, root="service.submit") / lookups
