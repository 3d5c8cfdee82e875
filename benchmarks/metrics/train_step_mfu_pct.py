"""Model flops of every step finished in the window over the window's
wall time, as a share of the chips' published bf16 peak."""


def read(run):
    w = run["work"]
    return 100.0 * w["flops"] / w["window_s"] \
        / (w["chips"] * run["peaks"]["bf16_flops"])
