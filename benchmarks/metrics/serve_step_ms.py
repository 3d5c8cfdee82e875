"""Mean wall time of one ``EngineLoop.iterate`` in the window, from the
harness's span around it."""


def read(run):
    lo, hi = run["window"]
    d = run["spans"].durations("serve_iterate", lo, hi)
    return 1e3 * sum(d) / len(d) if d else None
