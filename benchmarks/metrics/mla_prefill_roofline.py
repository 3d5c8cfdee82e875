"""The latent-attention prefill kernel's share of its roofline, which is
compute.  Least time: the causal query-key pairs of every prefill chunk
dispatched in the window (the program's dispatch log) at the
NON-absorbed form's 2 x (192 + 128) x 128 flops a pair and layer
(``models/pangu_ultra_moe.mla_prefill_least_s``) over the published peak;
the kernel runs the absorbed form, 3.4 times that arithmetic, so this
share says what that choice costs.  Over the kernel's device time in
the trace, by its name."""

KERNEL = r"^mla_prefill_attention(\.\d+)?\[tpu_custom_call\]"


def read(run):
    from benchmarks.harness.models import pangu_ultra_moe as model

    got = model.window_log(run)
    if got is None:
        return None
    sz, rows = got
    seconds = run["trace"].op_seconds_matching(KERNEL)
    if seconds <= 0.0:
        return None
    pairs = sum(r[3] for r in rows if r[1] == "prefill")
    return 100.0 * model.mla_prefill_least_s(sz, pairs, run["peaks"]) \
        / seconds
