"""Blocking device-to-host reads the engine makes per cycle: the sum of
`ServeReport.host_syncs` over the sum of `ServeReport.cycles`, over the
window's waves (prompt draws, delivered payloads, sampling keys, step
tokens; the Radio's own reads are not counted)."""


def read(run):
    reps = [w.report for w in run.cell.waves]
    if not reps or not all(hasattr(r, "host_syncs") for r in reps):
        return None
    cycles = sum(r.cycles for r in reps)
    return sum(r.host_syncs for r in reps) / cycles if cycles else None
