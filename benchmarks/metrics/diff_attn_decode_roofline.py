"""The differential-attention decode kernel's share of its roofline, which
is memory bandwidth.  Least time: the K and V rows of every key its calls
in the window attended (the program's dispatch log, host scheduler state:
the full layer's cache once for itself and once a cross layer, over decode
rows and a prompt's taken lane; a window of keys a window layer, over
decode rows) over the published bandwidth
(``models/phi4_flash.diff_attn_decode_least_s``), over the kernel's device
time in the trace, by its name."""

KERNEL = r"^diff_attn_decode(\.\d+)?\[tpu_custom_call\]"


def read(run):
    from benchmarks.harness.models import phi4_flash as model

    got = model.window_log(run)
    if got is None:
        return None
    sz, rows = got
    seconds = run["trace"].op_seconds_matching(KERNEL)
    if seconds <= 0.0:
        return None
    full = sum(r[6]["full_keys"] for r in rows)
    # a prefill chunk's window layers take the XLA form, not the kernel
    window = sum(r[6]["window_keys"] for r in rows if r[1] == "decode")
    return 100.0 * model.diff_attn_decode_least_s(
        sz, full, window, run["peaks"]) / seconds
