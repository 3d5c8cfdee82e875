"""The grouped-query decode kernel's share of its roofline, which is
memory bandwidth.  Least time: the K and V rows of every key its calls in
the window attended (the program's dispatch log, host scheduler state: a
full layer's whole context, a window layer's window, over decode rows)
over the published bandwidth (``models/cohere2_moe.gqa_decode_least_s``),
over the kernel's device time in the trace, by its name."""

KERNEL = r"^gqa_decode_attention(\.\d+)?\[tpu_custom_call\]"


def read(run):
    from benchmarks.harness.models import cohere2_moe as model

    got = model.window_log(run)
    if got is None:
        return None
    sz, rows = got
    seconds = run["trace"].op_seconds_matching(KERNEL)
    if seconds <= 0.0:
        return None
    decode = [r[6] for r in rows if r[1] == "decode"]
    return 100.0 * model.gqa_decode_least_s(
        sz, sum(x["full_keys"] for x in decode),
        sum(x["window_keys"] for x in decode), run["peaks"]) / seconds
