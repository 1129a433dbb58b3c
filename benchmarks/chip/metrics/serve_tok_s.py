"""Tokens served per second: every token served to the window's
requests over the window, from the start of its first wave to the end of
its last. For the classifier a token is a label."""


def read(run):
    return (sum(len(r.tokens) for _, _, r in run.cell.results())
            / run.window_s)
