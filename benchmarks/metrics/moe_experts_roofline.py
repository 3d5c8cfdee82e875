"""The routed experts' grouped matmuls' share of their roofline.  Least
time (``models/pangu_ultra_moe.moe_experts_least_s``): the larger of the
weight bytes of the experts each call touched (the device counter, read
per dispatch into the program's dispatch log) plus a row in and out per
assignment over the published bandwidth, and 6 x 7680 x 2048 flops an
assignment over the published peak; over the device time of the Pallas
grouped-matmul kernel (``gmm``, three calls a layer) in the trace."""

KERNEL = r"^gmm(\.\d+)?\[tpu_custom_call\]"


def read(run):
    from benchmarks.harness.models import pangu_ultra_moe as model

    got = model.window_log(run)
    if got is None:
        return None
    sz, rows = got
    seconds = run["trace"].op_seconds_matching(KERNEL)
    rows = [r for r in rows if r[4] is not None]
    if seconds <= 0.0 or not rows:
        return None
    return 100.0 * model.moe_experts_least_s(
        sz, sum(r[4] for r in rows), sum(r[5] for r in rows),
        run["peaks"]) / seconds
