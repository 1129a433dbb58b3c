"""Host time per engine cycle besides waiting on the steps: the
engine's `serve.cycle` spans less its `serve.prefill.wait` and
`serve.decode.wait` spans, over the count of `serve.cycle`, summed over
the window's waves (`ServeReport.spans`)."""
from benchmarks.chip import engine_spans


def read(run):
    t = engine_spans.totals(run)
    if not t or "serve.cycle" not in t:
        return None
    s, n = t["serve.cycle"]
    for wait in ("serve.prefill.wait", "serve.decode.wait"):
        s -= t.get(wait, (0.0, 0))[0]
    return 1e3 * s / n
