"""Set-up: from the start of the process to the start of the window
(imports, weights, compiling or reading the persistent cache, the warm
pass)."""


def read(run):
    return run.setup_s
