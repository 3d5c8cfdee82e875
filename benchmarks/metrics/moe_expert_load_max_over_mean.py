"""How unevenly the router loaded the held experts: the busiest
expert's assignments over the mean, from the device counter's running
totals (the program's dispatch log); 1.0 is even."""


def read(run):
    try:
        from mpi_tensorflow_tpu.utils import dispatch_log
    except ImportError:
        return None
    totals = dispatch_log.snapshot()["totals"]
    if not totals or not sum(totals):
        return None
    return max(totals) * len(totals) / sum(totals)
