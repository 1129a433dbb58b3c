"""Host time of billed radio sends per admitted request: the engine's
`serve.uplink` and `serve.downlink` spans over the count of
`serve.admit`, summed over the window's waves (`ServeReport.spans`)."""
from benchmarks.chip import engine_spans


def read(run):
    t = engine_spans.totals(run)
    if not t or "serve.admit" not in t:
        return None
    s = sum(t.get(k, (0.0, 0))[0] for k in ("serve.uplink",
                                             "serve.downlink"))
    return 1e3 * s / t["serve.admit"][1]
