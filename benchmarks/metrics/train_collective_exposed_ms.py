"""Per step, time inside collective operations while no other operation
ran on that device (mean over chips).  Nothing to read on one chip."""


def read(run):
    tr = run["trace"]
    if tr is None or tr.collective_s == 0.0:
        return None
    return 1e3 * tr.collective_exposed_s / run["work"]["steps"]
