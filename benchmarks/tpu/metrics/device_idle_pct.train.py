"""Share of the traced window in which no operation runs on the device."""


def read(run):
    return run.trace.idle_pct
