"""``correct`` comes out false when the timed path is broken underneath,
and the controls fail their limits.  Each case drives the rest of a run
(set-up, window, reference, checks) at a tiny size on the CPU, past the
harness's look for a chip."""
import dataclasses
import json
from pathlib import Path

import numpy as np
import pytest

import harness
import run

HERE = Path(__file__).resolve().parent
SEED = 2**32 + 17


def cpu_devices():
    import jax
    return jax.devices("cpu")[:1]


def tiny_train(name):
    cell = harness.find_cell(name)
    config = json.loads(json.dumps(cell.config))
    config.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
                  intermediate_size=160, vocab_size=256, num_hidden_layers=2)
    config["vision"] = {"num_prefix_tokens": 8, "patch_embed_dim": 32}
    config["train"]["text_len"] = 16
    mix = dict(cell.mix, warmup_steps=4)
    if mix["profiler"] is not None:
        # fewer drain lengths to warm: each compiles in interpret mode
        mix["profiler"] = dict(mix["profiler"], max_rows_per_sync=4)
    return dataclasses.replace(cell, config=config, mix=mix)


def measure(cell):
    result, checks = run.measure(cell, SEED, 0.3, False, cpu_devices())
    return result, {c.name: c for c in checks}


@pytest.fixture
def no_pallas_hist(monkeypatch):
    import repro.core.detector as det
    monkeypatch.setattr(det, "pallas_hist_for", lambda backend: False)


# -- training -------------------------------------------------------------

TRAIN = ["internvl2.train_attached", "internvl2.train_detached"]


@pytest.mark.parametrize("name", TRAIN)
def test_sound_training_is_correct(name, no_pallas_hist, monkeypatch):
    windows = []
    start = run.WindowClock.start

    def keep(clock):
        windows.append(clock)
        start(clock)
    monkeypatch.setattr(run.WindowClock, "start", keep)
    result, checks = measure(tiny_train(name))
    assert result["correct"], checks
    # set-up compiles (the step, the norms, every drain length) are not
    # the window's
    assert windows[0].compiles_in_window == 0


def _train_fault(kind):
    """The step the Trainer drives, broken: the state comes back
    unchanged, half the batch is dropped, or the loss is altered."""
    import jax
    from repro.train.step import make_train_step

    def install(job):
        step = jax.jit(make_train_step(job.mcfg, job.opt_cfg))

        def broken(params, opt_state, batch, err):
            if kind == "half_batch":
                batch = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
            p, o, metrics, e = step(params, opt_state, batch, err)
            if kind == "state_unchanged":
                p, o = params, opt_state
            if kind == "loss_altered":
                metrics = dict(metrics, loss=metrics["loss"] * 1.1)
            return p, o, metrics, e
        job.step = broken
    return install


def _one_driver(cell, monkeypatch):
    """The cell's driver module, loaded once, so a test can patch it."""
    driver = cell.driver()
    load = harness.load_module
    monkeypatch.setattr(harness, "load_module", lambda path, name=None: (
        driver if path.parent.name == "drivers" else load(path, name)))
    return driver


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch",
                                  "loss_altered"])
def test_broken_training_is_not_correct(kind, monkeypatch, no_pallas_hist):
    cell = tiny_train(TRAIN[1])
    driver = _one_driver(cell, monkeypatch)
    setup = driver.Job.setup

    def broken_setup(job):
        setup(job)
        _train_fault(kind)(job)
    monkeypatch.setattr(driver.Job, "setup", broken_setup)
    result, checks = measure(cell)
    assert not result["correct"], checks


def test_a_slice_lost_from_the_session_log_is_not_correct(
        monkeypatch, no_pallas_hist):
    """The tracer loses the data loader's fifth slice where it records
    it; the report, folded from the same events, still agrees with the
    reference, so only the log's audit can see it."""
    from repro.core.tracer import Tracer
    cell = tiny_train(TRAIN[0])
    begin, end = Tracer.begin, Tracer.end
    seen = {}

    def lossy_begin(self, wid, *a, **k):
        n = seen[wid] = seen.get(wid, 0) + 1
        if self.worker_names()[wid] == "data_loader" and n == 5:
            return 0
        return begin(self, wid, *a, **k)

    def lossy_end(self, wid):
        n = seen.get(("end", wid), 0) + 1
        seen[("end", wid)] = n
        if self.worker_names()[wid] == "data_loader" and n == 5:
            return None
        return end(self, wid)
    monkeypatch.setattr(Tracer, "begin", lossy_begin)
    monkeypatch.setattr(Tracer, "end", lossy_end)
    result, checks = measure(cell)
    assert not result["correct"], checks
    assert not checks["log_audit"].ok
    assert checks["report_per_worker_gap"].ok


def test_report_control_fails():
    import controls
    cell = tiny_train(TRAIN[0])
    got = controls.report_control(cell.config, SEED, seconds=1.0,
                                  mix=cell.mix)
    limits = cell.mix["limits"]
    assert any(got[k] > limits[f"report_{k}"] for k in got), got


def test_training_control_fails():
    import jax.numpy as jnp

    from drivers import train
    cell = tiny_train(TRAIN[1])
    ref = train.reference(cell.config, SEED)
    low = train.reference(cell.config, SEED, mm_dtype=jnp.float8_e4m3fn)
    got = train.compare(ref, low)
    limits = cell.mix["limits"]
    assert any(got[k] > limits[k] for k in got), got
    assert np.isfinite(list(got.values())).all()
