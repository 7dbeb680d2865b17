"""Trace, lowering and compile-or-load milliseconds per tuning job, from
the program's ``jit.*`` counters under its ``tune.job`` spans over those
spans: each job's tuner traces and loads its step and eval programs
again."""


def read(run):
    try:
        from repro.obs import device
    except ImportError:              # a program without device spans
        return None
    snap = device.snapshot()
    jobs = device.total(snap["spans"], "count", span="tune.job")
    if not jobs:
        return None
    return 1e3 * device.jit_seconds(snap, root="tune.job") / jobs
