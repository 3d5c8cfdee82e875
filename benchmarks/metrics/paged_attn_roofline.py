"""The paged-attention kernel's share of its roofline, which is memory
bandwidth: the K and V bytes the live contexts of every call in the
window oblige it to read (``flops.paged_attention_bytes``) over the
published HBM bandwidth, divided by the kernel's device time in the
trace."""

# the Mosaic custom calls of the device trace (``trace_reduce.op_name``
# tags them); the paged-attention kernel is the only one the serving
# programs hold
KERNEL = r"\[tpu_custom_call\]"


def read(run):
    tr = run["trace"]
    if tr is None:
        return None
    seconds = tr.op_seconds_matching(KERNEL)
    if seconds <= 0.0:
        return None
    least = run["work"]["paged_bytes"] / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / seconds
