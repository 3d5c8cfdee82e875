"""Mean time of one pass of the loop body on the host (slice the batch,
``shard_batch``, dispatch the step), from the harness's span."""


def read(run):
    lo, hi = run["window"]
    d = run["spans"].durations("train_host_feed", lo, hi)
    return 1e3 * sum(d) / len(d) if d else None
