"""Plain CMetric fold, pairing and path ranking in numpy.

The paper's §2/§4 algorithm written straight from its definition, with no
code of the profiler: time splits into intervals at every switch; each
active worker earns ``T_i / n_i`` of interval ``i``; a worker's slice
CMetric is the sum over the intervals it ran through; a slice is critical
when its average parallelism ``duration / cmetric`` is under ``n_min``;
critical slices merge by call path and paths rank by summed CMetric.

``dtype`` is float64 for the reference.  The control computes the same in
bfloat16, the precision below the device fold's float32.
"""
from __future__ import annotations

import dataclasses

import numpy as np

ACTIVATE, DEACTIVATE = 1, -1


@dataclasses.dataclass
class Outcome:
    """What a fold of a capture answers, in the form both sides are
    compared in."""

    per_worker: np.ndarray                    # float64[W]
    paths: list[tuple[tuple[int, ...], float]]  # ranked (path, cmetric)
    crit_worker: np.ndarray                   # int32[C] critical slices,
    crit_end_ns: np.ndarray                   # int64[C] by worker and end


def from_report(rep) -> Outcome:
    """A profiler report (per-worker CMetric, ranked paths, critical
    table) in the form the reference is compared in."""
    ct = rep.critical_table
    return Outcome(
        per_worker=np.asarray(rep.per_worker, np.float64),
        paths=[(tuple(p.stack), float(p.cmetric)) for p in rep.paths],
        crit_worker=np.asarray(ct.worker, np.int32),
        crit_end_ns=np.asarray(ct.end_ns, np.int64))


def _cast(x, dtype):
    return np.asarray(x, np.float64).astype(dtype)


def fold(times, workers, deltas, stacks, num_workers: int, n_min: float,
         paths: list[tuple[int, ...]], top_n: int = 10,
         dtype=np.float64) -> Outcome:
    """Fold a clean, time-sorted capture.  ``stacks[i]`` is the path id of
    switch-out ``i``.  Raises on a capture whose workers do not alternate
    switch-in and switch-out."""
    times = np.asarray(times, np.int64)
    workers = np.asarray(workers, np.int64)
    d = np.asarray(deltas, np.int64)
    t = _cast((times - times[0]) * 1e-9, dtype)
    dt = np.concatenate([np.zeros(1, dtype), (t[1:] - t[:-1]).astype(dtype)])
    n_before = np.cumsum(d) - d          # active while interval i ran
    if n_before.min() < 0:
        raise ValueError("switch-out before switch-in")
    share = np.where(n_before > 0,
                     dt / _cast(np.maximum(n_before, 1), dtype),
                     _cast(0.0, dtype)).astype(dtype)
    gcm = np.cumsum(share, dtype=dtype)   # running sum of T_i / n_i

    # a worker's events alternate in, out: pair each out with the event
    # before it in the worker's own order
    order = np.argsort(workers, kind="stable")
    ws, ds = workers[order], d[order]
    first = np.concatenate([[True], ws[1:] != ws[:-1]])
    pos = np.arange(ws.size) - np.maximum.accumulate(
        np.where(first, np.arange(ws.size), 0))
    if np.any(ds[pos % 2 == 0] != ACTIVATE) or np.any(
            ds[pos % 2 == 1] != DEACTIVATE):
        raise ValueError("workers do not alternate switch-in and switch-out")
    out_sorted = pos % 2 == 1
    out_i = order[out_sorted]
    in_i = order[np.flatnonzero(out_sorted) - 1]
    back = np.argsort(out_i, kind="stable")       # event order
    out_i, in_i = out_i[back], in_i[back]

    slice_cm = (gcm[out_i] - gcm[in_i]).astype(dtype)
    dur = (t[out_i] - t[in_i]).astype(dtype)
    positive = slice_cm > 0
    av = np.where(positive,
                  np.asarray(dur, np.float64)
                  / np.where(positive, np.asarray(slice_cm, np.float64), 1.0),
                  np.maximum(n_before[out_i], 1).astype(np.float64))
    cm64 = np.asarray(slice_cm, np.float64)
    per_worker = np.bincount(workers[out_i], weights=cm64,
                             minlength=num_workers)

    crit = av < n_min
    # merge by call path; an id with no path (no stack) is the empty path
    ids = np.asarray(stacks, np.int64)[out_i][crit]
    uniq, first_seen, inv = np.unique(ids, return_index=True,
                                      return_inverse=True)
    id_cm = np.bincount(inv, weights=cm64[crit], minlength=uniq.size)
    totals: dict[tuple[int, ...], float] = {}
    for k in np.argsort(first_seen, kind="stable"):    # first-seen order
        sid = int(uniq[k])
        path = tuple(paths[sid]) if 0 <= sid < len(paths) else ()
        totals[path] = totals.get(path, 0.0) + float(id_cm[k])
    ranked = sorted(totals, key=lambda p: -totals[p])[:top_n]
    return Outcome(
        per_worker=per_worker,
        paths=[(p, totals[p]) for p in ranked],
        crit_worker=workers[out_i][crit].astype(np.int32),
        crit_end_ns=times[out_i][crit])


def crit_disagreement(a: Outcome, ref: Outcome, tol_ns: int = 20_000
                      ) -> float:
    """Share of the reference's critical slices on which ``a`` disagrees:
    slices critical on one side only, over the reference's count.  A slice
    of ``a`` matches the reference slice of its worker whose end lies
    nearest, within ``tol_ns`` (``a``'s ends may be rounded)."""
    span = int(max(ref.crit_end_ns.max(initial=0),
                   a.crit_end_ns.max(initial=0))) + 1
    rk = ref.crit_worker.astype(np.int64) * span + ref.crit_end_ns
    ak = a.crit_worker.astype(np.int64) * span + a.crit_end_ns
    if rk.size == 0:
        return float(ak.size > 0)
    rk = np.sort(rk)
    j = np.searchsorted(rk, ak)
    lo, hi = np.clip(j - 1, 0, rk.size - 1), np.clip(j, 0, rk.size - 1)
    near = np.where(np.abs(ak - rk[lo]) <= np.abs(ak - rk[hi]), lo, hi)
    hit = np.abs(ak - rk[near]) <= tol_ns
    matched = np.unique(near[hit]).size
    only_a = ak.size - matched
    only_ref = rk.size - matched
    return (only_a + only_ref) / max(rk.size, 1)


def compare(a: Outcome, ref: Outcome) -> dict[str, float]:
    """The numbers a profiler report is judged by (each must stay under
    its limit):

    * ``per_worker_gap``   worst relative gap of a worker's CMetric;
    * ``path_rank_moves``  ranks whose path differs from the reference's;
    * ``path_cm_gap``      worst relative gap of a ranked path's CMetric;
    * ``crit_disagree``    share of critical slices the sides disagree on.
    """
    pw_ref = np.abs(ref.per_worker)
    gap = np.abs(np.asarray(a.per_worker, np.float64) - ref.per_worker)
    per_worker_gap = float(np.max(gap / np.maximum(pw_ref, 1e-300)))
    moves = sum(1 for i, (p, _) in enumerate(ref.paths)
                if i >= len(a.paths) or a.paths[i][0] != p)
    a_cm = dict(a.paths)
    path_gap = max((abs(a_cm.get(p, 0.0) - cm) / cm
                    for p, cm in ref.paths if cm > 0), default=0.0)
    return {"per_worker_gap": per_worker_gap,
            "path_rank_moves": float(moves),
            "path_cm_gap": float(path_gap),
            "crit_disagree": crit_disagreement(a, ref)}
