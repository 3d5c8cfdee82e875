"""The grouped-query prefill kernel's share of its roofline.  Least time:
the larger of the visible (query, key) pairs of every prefill chunk
dispatched in the window at 4 x heads x head_dim flops a pair and layer
over the published peak, and the bytes of the keys, queries and outputs
those pairs touch over the published bandwidth (the program's dispatch
log; ``models/cohere2_moe.gqa_prefill_least_s``); over the kernel's device
time in the trace, by its name.  A window layer's pairs stop at its
window: keys below it oblige nothing."""

KERNEL = r"^gqa_prefill_attention(\.\d+)?\[tpu_custom_call\]"


def read(run):
    from benchmarks.harness.models import cohere2_moe as model

    got = model.window_log(run)
    if got is None:
        return None
    sz, rows = got
    seconds = run["trace"].op_seconds_matching(KERNEL)
    if seconds <= 0.0:
        return None
    chunks = [r for r in rows if r[1] == "prefill"]
    work = {k: sum(r[6][k] for r in chunks) for k in
            ("full_keys", "window_keys", "full_rows", "window_rows")}
    work["queries"] = sum(r[2] for r in chunks)
    return 100.0 * model.gqa_prefill_least_s(sz, work, run["peaks"]) \
        / seconds
