"""Model flops of every prompt and output token processed in the window
(matmuls, attention over the live context, head at emitted tokens) over
the window's wall time, as a share of the chip's published bf16 peak."""


def read(run):
    w = run["work"]
    return 100.0 * w["flops"] / w["window_s"] \
        / (w["chips"] * run["peaks"]["bf16_flops"])
