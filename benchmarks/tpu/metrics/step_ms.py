"""Training step time: window wall time over the steps it completed,
loader wait and host work between steps included."""


def read(run):
    if not run.clock.marks:
        return None
    return 1e3 * run.clock.elapsed / len(run.clock.marks)
