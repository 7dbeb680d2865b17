"""Host milliseconds per tuning step spent drawing and uploading its
batch, from the program's spans: ``tune.batch`` and the ``tuner.upload``
children of ``tune.step``, over the ``tune.step`` spans. Hidden while
steps are queued on the chip, exposed after every sync (each eval)."""


def read(run):
    try:
        from repro.obs import device
    except ImportError:              # a program without device spans
        return None
    spans = device.snapshot()["spans"]
    steps = device.total(spans, "count", span="tune.step")
    if not steps:
        return None
    host = (device.total(spans, "total_s", span="tune.batch")
            + device.total(spans, "total_s", span="tuner.upload",
                           parent="tune.step"))
    return 1e3 * host / steps
