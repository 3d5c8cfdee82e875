"""Device-busy time per step: the union of device-op intervals in the
traced window (mean over chips) over the steps it held."""


def read(run):
    tr = run["trace"]
    if tr is None:
        return None
    return 1e3 * tr.busy_s / run["work"]["steps"]
