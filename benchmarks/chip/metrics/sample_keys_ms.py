"""Host time per engine cycle spent deriving sampling keys: the
engine's `serve.keys` spans over the count of `serve.cycle`, summed over
the window's waves (`ServeReport.spans`). Greedy decoding derives none,
and reads nothing."""
from benchmarks.chip import engine_spans


def read(run):
    t = engine_spans.totals(run)
    if not t or "serve.keys" not in t or "serve.cycle" not in t:
        return None
    return 1e3 * t["serve.keys"][0] / t["serve.cycle"][1]
