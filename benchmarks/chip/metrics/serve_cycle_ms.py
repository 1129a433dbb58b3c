"""Host time per engine cycle: the window over the cycles the engine's
reports count (`ServeReport.cycles`, one batched step over the slots)."""


def read(run):
    cycles = sum(w.report.cycles for w in run.cell.waves)
    return 1e3 * run.window_s / cycles if cycles else None
