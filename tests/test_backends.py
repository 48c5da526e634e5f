"""The two CMetric fold paths: dispatch, the name check at construction,
and the import layering (kernels under core, Pallas loaded lazily)."""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import (LogSource, ProfileSession, StackRegistry, TagRegistry,
                        Tracer, compute, compute_numpy, detect_offline,
                        synthetic_log)
from repro.core import cmetric

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src")


def _modules_after(code: str) -> list[str]:
    """Run ``code`` in a fresh interpreter; returns its ``sys.modules``."""
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-c",
         code + "\nimport sys\nprint('\\n'.join(sys.modules))"],
        env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout.split()


def test_two_fold_backends():
    assert cmetric.BACKENDS == ("numpy", "pallas")
    for name in cmetric.BACKENDS:
        assert cmetric.check_backend(name) == name


def test_unknown_backend_raises_with_available_names():
    with pytest.raises(KeyError, match="numpy, pallas"):
        cmetric.check_backend("no-such-backend")
    log = synthetic_log(np.random.default_rng(0), 2, 3)
    for name in ("vector", "stream"):
        with pytest.raises(KeyError, match=name):
            compute(log, backend=name)
        with pytest.raises(KeyError, match=name):
            cmetric.fold_chunk(cmetric.FoldCarry.init(2), log, backend=name)


def test_compute_dispatches_through_registry():
    rng = np.random.default_rng(0)
    log = synthetic_log(rng, 4, 10)
    a = compute(log, backend="numpy")
    b = compute_numpy(log)
    np.testing.assert_array_equal(a.per_worker, b.per_worker)
    np.testing.assert_array_equal(a.table.cm, b.table.cm)
    c = compute(log, backend="pallas")
    d = cmetric.compute_pallas(log)
    np.testing.assert_array_equal(c.per_worker, d.per_worker)
    np.testing.assert_allclose(c.per_worker, b.per_worker, rtol=1e-4,
                               atol=1e-6)


def test_pallas_registration_is_lazy():
    # the device path imports the kernels only when it runs
    mods = _modules_after("import repro.core")
    assert "repro.core.cmetric" in mods
    assert not [m for m in mods if m.startswith("jax.experimental.pallas")]
    assert not [m for m in mods if m.startswith("repro.kernels")]


def test_kernels_import_nothing_from_core():
    mods = _modules_after(
        "import repro.kernels, repro.kernels.attention, repro.kernels.ref")
    assert "repro.kernels.ops" in mods
    assert not [m for m in mods if m.startswith("repro.core")]


def _log():
    return synthetic_log(np.random.default_rng(1), 3, 4)


def _replay_spec():
    from repro.core.whatif import ReplaySpec
    return ReplaySpec(log_provider=_log, tags=TagRegistry(),
                      stacks=StackRegistry(), n_min=1.5, backend="vector")


def _service(tmp_path):
    from repro.fleet.service import ProfilerService
    return ProfilerService.from_fleet_dir(str(tmp_path),
                                          fold_backend="vector")


CONSTRUCTORS = {
    "Tracer": lambda tmp_path: Tracer(fold_backend="vector"),
    # a source-backed session builds no Tracer: its own check must fire
    "ProfileSession": lambda tmp_path: ProfileSession(
        LogSource(_log()), fold_backend="vector"),
    "ProfileSession.offline": lambda tmp_path: ProfileSession.offline(
        _log(), n_min=1.5, backend="vector"),
    "detect_offline": lambda tmp_path: detect_offline(
        _log(), TagRegistry(), StackRegistry(), 1.5, backend="vector"),
    "ReplaySpec": lambda tmp_path: _replay_spec(),
    "ProfilerService": _service,
}


@pytest.mark.parametrize("entry", list(CONSTRUCTORS))
def test_unknown_fold_backend_rejected_at_construction(entry, tmp_path):
    with pytest.raises(KeyError, match="unknown CMetric backend 'vector'"):
        CONSTRUCTORS[entry](tmp_path)
