"""setup_s: seconds from the process's start to the window's first step:
imports, weights, compilation or the compile cache, warm-up, calibration."""


def read(run):
    return run.setup_s
