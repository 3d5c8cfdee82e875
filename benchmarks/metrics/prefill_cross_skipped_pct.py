"""Share of the window's prefill lanes on which the cross-decoder (the
full layer's attention and MLP, every later layer, the head) was NOT run:
the architecture's linear-time prefill.  From the program's dispatch log
(``utils/dispatch_log``: lanes skipped over tokens through the scans, a
prefill dispatch); 100 x (1 - 1 / prompt length) where only a prompt's
last lane is taken."""


def read(run):
    from benchmarks.harness.models import phi4_flash as model

    got = model.window_log(run)
    if got is None:
        return None
    rows = [r[6] for r in got[1] if r[1] == "prefill"]
    lanes = sum(r["scanned"] for r in rows)
    if not lanes:
        return None
    return 100.0 * sum(r["skipped_lanes"] for r in rows) / lanes
