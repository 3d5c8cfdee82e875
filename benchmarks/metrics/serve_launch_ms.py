"""Mean over the window's engine steps of the time the host spent calling
into compiled programs (``serve.launch``: each call's argument transfer
and enqueue), from the program's phase spans (``harness/engine_steps``)."""


def read(run):
    from benchmarks.harness import engine_steps

    return engine_steps.mean_ms(run, lambda r: r["phases"]["serve.launch"])
