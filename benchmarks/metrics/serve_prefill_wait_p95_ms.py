"""95th percentile, over requests submitted in the window, of submit to
the first prefill chunk (the program's ``EngineTracer`` span events)."""

import numpy as np


def read(run):
    waits = run["work"].get("prefill_wait_s")
    if not waits:
        return None
    return 1e3 * float(np.percentile(np.asarray(waits), 95))
