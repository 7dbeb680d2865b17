"""Share of the chip's bf16 peak that the Eqn-1 score calls of the
window required (``flops.forward`` per call) over the window's seconds."""


def read(run):
    if not run["required_flops"] or not run["peak_flops"]:
        return None
    return 100.0 * run["required_flops"] / (run["window_s"]
                                            * run["peak_flops"])
