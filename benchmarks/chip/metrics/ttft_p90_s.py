"""90th percentile (nearest rank) over every request of the window of
the time from its admission to its first token, as the engine stamps it
on the host's clock (`RequestResult.ttft_s`; the wait in the queue
before admission is not in it)."""


def percentile(vals, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least q% of
    the values at or below it."""
    vals = sorted(vals)
    k = max(1, -(-len(vals) * q // 100))
    return float(vals[int(k) - 1])


def read(run):
    return percentile([r.ttft_s for _, _, r in run.cell.results()
                       if r.ttft_s >= 0], 90)
