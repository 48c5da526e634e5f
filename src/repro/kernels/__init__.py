"""Pallas TPU kernels for the profiler's post-processing hot spots.

``cmetric_fold`` — coupled prefix scans (active count + global_cm) over the
event stream; ``tag_hist`` — sample-tag frequency / weighted-CMetric tables.
Each kernel has a pure-jnp oracle in ``ref.py`` and a jit'd wrapper in
``ops.py``, which compiles them natively on a TPU and interprets them
(``interpret=True``) on any other backend.
"""
from repro.kernels import ops, ref
from repro.kernels.ops import cmetric_fold, compute_pallas, tag_histogram

__all__ = ["ops", "ref", "cmetric_fold", "compute_pallas", "tag_histogram"]
