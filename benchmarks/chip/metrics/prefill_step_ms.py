"""Device time of one prefill chunk step: the device time of the
engine's `jit_prefill_sample` programs (every bucket) over their runs."""


def read(run):
    s, n = run.summary.program_time("jit_prefill_sample")
    return 1e3 * s / n if n else None
