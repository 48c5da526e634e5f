"""XLA compiles inside the measured window (persistent-cache hits are
not compiles)."""


def read(run):
    return float(run.compiles_in_window)
