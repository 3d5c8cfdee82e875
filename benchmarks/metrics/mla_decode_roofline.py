"""The latent-attention decode kernel's share of its roofline.  Least
time: the cached tokens every decode dispatch of the window attended
(the program's dispatch log, host scheduler state), each read once per
row and layer at 576 values (``models/pangu_ultra_moe.mla_decode_least_s``:
the larger of those bytes over the published bandwidth and the absorbed
form's flops over the published peak; on this chip the two are within
1%), over the kernel's device time in the trace, by its name."""

KERNEL = r"^mla_decode_attention(\.\d+)?\[tpu_custom_call\]"


def read(run):
    from benchmarks.harness.models import pangu_ultra_moe as model

    got = model.window_log(run)
    if got is None:
        return None
    sz, rows = got
    seconds = run["trace"].op_seconds_matching(KERNEL)
    if seconds <= 0.0:
        return None
    attended = sum(r[3] for r in rows if r[1] == "decode")
    return 100.0 * model.mla_decode_least_s(sz, attended, run["peaks"]) \
        / seconds
