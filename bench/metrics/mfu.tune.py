"""Share of the chip's bf16 peak that the tuning steps and evals of the
window required (``flops.tune_step`` per step, ``flops.forward`` per
eval) over the window's seconds."""


def read(run):
    if not run["required_flops"] or not run["peak_flops"]:
        return None
    return 100.0 * run["required_flops"] / (run["window_s"]
                                            * run["peak_flops"])
