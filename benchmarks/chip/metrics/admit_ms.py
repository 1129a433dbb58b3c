"""Host time to admit one request into a slot: the engine's
`serve.admit` spans (prompt draw, uplink, pages or slot zeroed) over
their count, summed over the window's waves (`ServeReport.spans`)."""
from benchmarks.chip import engine_spans


def read(run):
    t = engine_spans.totals(run)
    if not t or "serve.admit" not in t:
        return None
    s, n = t["serve.admit"]
    return 1e3 * s / n
