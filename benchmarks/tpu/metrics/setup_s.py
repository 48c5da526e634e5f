"""Set-up: process start to the window's first timed call (imports,
inputs from the seed, loading or compiling every program, warm-up)."""


def read(run):
    return run.setup_s
