"""Set-up seconds: from the process's start to the window's, covering
imports, data generation, placement, compiles and the warm-up op."""


def read(run):
    return run.setup_s
