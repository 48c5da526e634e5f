"""Host time of the job's own loop per step: the trainer thread's wait for
the loader (``train/loader_wait``), its batch copy to the device
(``train/h2d``) and its work after each step (``train/host``: history, log
line, checkpoint hand-off), from the program's spans, per step in the
traced part of the window.  Each of these spans sits in the gap between
two steps, so it is counted between the first and the last step traced
whole, over the gaps there."""

HOST_SPANS = ("train/loader_wait", "train/h2d", "train/host")
STEP_SPAN = "train/step"


def window_records(run):
    """The program's span records that start in the window (the records
    exist only while the trace runs, so this is its traced part), the start
    of the first and of the last step among them, and the number of steps
    between the two; or None where the program records no spans or fewer
    than two steps were traced whole."""
    try:
        from repro.obs import spans
    except ImportError:
        return None
    recs = spans.records(int(run.clock.t0 * 1e9))
    starts = [r[2] for r in recs if r[0] == STEP_SPAN]
    if len(starts) < 2:
        return None
    return recs, min(starts), max(starts), len(starts) - 1


def read(run):
    window = window_records(run)
    if window is None:
        return None
    recs, first, last, steps = window
    ns = sum(end - start for name, _, start, end in recs
             if name in HOST_SPANS and first <= start < last)
    return 1e-6 * ns / steps
