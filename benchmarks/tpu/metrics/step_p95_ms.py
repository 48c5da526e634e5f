"""95th percentile of the per-iteration time (one step's start to the
next's) over every step of the window."""
import harness


def read(run):
    times = [run.clock.t0] + run.clock.marks
    if len(times) < 2:
        return None
    return 1e3 * harness.percentile(
        [b - a for a, b in zip(times, times[1:])], 95)
