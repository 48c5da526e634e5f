"""Mean host time of one live fold call (``profiler/fold``: the drained
chunk through the session's fold backend, its device prefix dispatched and
read back), less its wait for the device result (``profiler/fold_wait``,
which queues behind the job's step), in the traced part of the window."""
from pathlib import Path

import harness

_here = Path(__file__).parent
window_records = harness.load_module(
    _here / "trainer_host_ms_per_step.py").window_records
_host = harness.load_module(_here / "profiler_host_ms_per_step.py")

FOLD_SPAN = "profiler/fold"


def read(run):
    window = window_records(run)
    if window is None:
        return None
    recs, first, last, _ = window
    folds = [_host.host_ns(r, group)
             for _, group in _host.groups(recs, first, last)
             for r in group if r[0] == FOLD_SPAN]
    if not folds:
        return None
    return 1e-6 * sum(folds) / len(folds)
