"""Device time of one decode step: the device time of the engine's
`jit_step_sample` program over its runs in the trace."""


def read(run):
    s, n = run.summary.program_time("jit_step_sample")
    return 1e3 * s / n if n else None
