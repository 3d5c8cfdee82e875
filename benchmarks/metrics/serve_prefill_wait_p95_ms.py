"""95th percentile, over requests whose first prefill chunk fell in the
window, of submit to that chunk (the program's ``EngineTracer`` span
events; the waits ``serve_driver._prefill_wait`` gathers)."""

import numpy as np


def read(run):
    waits = run["work"].get("prefill_wait_s")
    if not waits:
        return None
    return 1e3 * float(np.percentile(np.asarray(waits), 95))
