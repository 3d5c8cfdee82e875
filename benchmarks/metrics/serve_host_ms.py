"""Mean over the window's engine steps of the host's own work an
iteration: the step's length (the sum of its phases' self times) less the
blocking token read (``serve.wait``) and the tracer's bookkeeping
(``serve.trace``), from the program's phase spans
(``harness/engine_steps``).  What the untraced program does on the host an
iteration."""


def read(run):
    from benchmarks.harness import engine_steps

    return engine_steps.mean_ms(
        run, lambda r: sum(r["phases"].values()) - r["phases"]["serve.wait"]
        - r["phases"]["serve.trace"])
