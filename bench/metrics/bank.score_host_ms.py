"""Host milliseconds per Eqn-1 call in which the chip has nothing queued,
from the program's own spans (``repro.obs.device``): the ``bank.score``
spans' seconds, less their ``tuner.sync`` children and the lookups'
trace and compile seconds (all in a lookup's first call), over the
``bank.score`` spans. Every call ends in a sync, so the chip waits
through the rest: prompt and eval-batch upload, dispatch."""


def read(run):
    try:
        from repro.obs import device
    except ImportError:              # a program without device spans
        return None
    snap = device.snapshot()
    spans = snap["spans"]
    calls = device.total(spans, "count", span="bank.score")
    if not calls:
        return None
    host = (device.total(spans, "total_s", span="bank.score")
            - device.total(spans, "total_s", span="tuner.sync",
                           parent="bank.score")
            - device.jit_seconds(snap, root="service.submit"))
    return 1e3 * host / calls
