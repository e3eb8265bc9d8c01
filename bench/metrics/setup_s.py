"""setup_s: process start to the opening of the measured window
(loading, data, weights, compiling or loading every program, warm-up)."""


def read(run):
    return run.setup_s
