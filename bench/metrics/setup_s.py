"""Set-up: seconds from the process's start to the window's opening
(imports, weights, engine, compiles or cache loads, warm-up)."""


def read(run):
    return run.setup_s
