"""The plain references and the arithmetic of the yardstick, on the CPU."""
import json
from pathlib import Path

import numpy as np
import pytest

import flops_bytes
import lm_data
import weights
from reference import cmetric_f64
from testdata import capture

HERE = Path(__file__).resolve().parent


def small_capture(seed=3, spans=25_000):
    return capture.generate(dict(capture.SMALL, parallel_spans=spans), seed)


def test_capture_size_does_not_follow_the_seed():
    a, b = small_capture(seed=1), small_capture(seed=2**31 + 9)
    assert len(a) == len(b) == 2 * (25_000 + 1)
    assert not np.array_equal(a.times, b.times)


def test_capture_refuses_too_few_spans():
    with pytest.raises(ValueError, match="fewer than"):
        small_capture(spans=10**6)


def test_reference_fold_agrees_with_the_program_oracle():
    from repro.core.detector import detect_offline
    cap = small_capture()
    log, tags, stacks = capture.to_program(cap)
    rep = detect_offline(log, tags, stacks, cap.n_min, backend="numpy")
    ref = cmetric_f64.fold(cap.times, cap.workers, cap.deltas, cap.stacks,
                           cap.num_workers, cap.n_min, cap.paths)
    np.testing.assert_allclose(ref.per_worker, rep.per_worker, rtol=1e-12)
    got = cmetric_f64.from_report(rep)
    assert [p for p, _ in got.paths] == [p for p, _ in ref.paths]
    assert ref.paths[0][0] == cap.paths[cap.serial_path]
    assert cmetric_f64.compare(got, ref) == {
        "per_worker_gap": pytest.approx(0.0, abs=1e-12),
        "path_rank_moves": 0.0,
        "path_cm_gap": pytest.approx(0.0, abs=1e-12),
        "crit_disagree": 0.0}


def test_reference_fold_refuses_a_broken_capture():
    cap = small_capture()
    deltas = cap.deltas.copy()
    deltas[np.flatnonzero(deltas == -1)[5]] = 1
    with pytest.raises(ValueError):
        cmetric_f64.fold(cap.times, cap.workers, deltas, cap.stacks,
                         cap.num_workers, cap.n_min, cap.paths)


def test_crit_disagreement_counts_both_sides():
    ref = cmetric_f64.Outcome(np.ones(2), [], np.array([0, 0, 1], np.int32),
                              np.array([100_000, 300_000, 100_000]))
    same = cmetric_f64.Outcome(np.ones(2), [], ref.crit_worker,
                               ref.crit_end_ns + 500)     # rounded ends
    assert cmetric_f64.crit_disagreement(same, ref) == 0.0
    one_off = cmetric_f64.Outcome(np.ones(2), [], np.array([0, 1], np.int32),
                                  np.array([100_000, 900_000]))
    # worker 0's second slice is missing and worker 1's is elsewhere
    assert cmetric_f64.crit_disagreement(one_off, ref) == pytest.approx(
        3 / 3)


def test_train_step_flops_match_a_hand_count():
    cfg = json.loads((HERE / "configs" / "internvl2.json").read_text())
    s = 256 + 1024
    d, f, v, kv_d = 2048, 8192, 92553, 1024
    per_layer = (2 * s * (2 * d * d + 2 * d * kv_d + 3 * d * f)
                 + 4 * s * s * d)
    fwd = 8 * per_layer + 2 * 256 * 1024 * d + 2 * s * d * v
    assert flops_bytes.lm_forward_flops(cfg, 1, 1024) == fwd
    assert flops_bytes.train_step_flops(cfg, 2, 1024) == 3 * 2 * fwd
    assert flops_bytes.train_step_flops(cfg, 2, 1024) == pytest.approx(
        11.29e12, rel=1e-3)


def test_peaks_reject_an_unknown_device():
    assert flops_bytes.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        flops_bytes.peaks("TPU v99")


def test_batches_repeat_from_the_seed_and_rows_differ():
    cfg = json.loads((HERE / "configs" / "internvl2.json").read_text())
    a, b = lm_data.for_job(cfg, 7), lm_data.for_job(cfg, 7)
    x, y = a.next_batch(), b.next_batch()
    assert x["tokens"].shape == (2, 1024)
    assert x["frontend"].shape == (2, 256, 1024)
    np.testing.assert_array_equal(x["tokens"], y["tokens"])
    assert not np.array_equal(x["tokens"][0], x["tokens"][1])
    assert not np.array_equal(a.next_batch()["tokens"], x["tokens"])


def test_weights_layout_is_the_programs_tree():
    import jax
    from repro.models import init_lm
    from drivers.train import model_config
    cfg = json.loads((HERE / "configs" / "internvl2.json").read_text())
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               intermediate_size=96, vocab_size=128, num_hidden_layers=2)
    cfg["vision"] = {"num_prefix_tokens": 4, "patch_embed_dim": 16}
    want = jax.eval_shape(lambda k: init_lm(k, model_config(cfg)),
                          jax.random.PRNGKey(0))
    got = weights.make(cfg, 5)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.shape == w.shape and g.dtype == w.dtype
    again = weights.make(cfg, 5)
    assert all(np.array_equal(a, b) for a, b in
               zip(jax.tree.leaves(got), jax.tree.leaves(again)))
    norms = np.asarray(weights.change_norms(cfg)(got, weights.key_of(5)))
    assert norms.shape == (len(weights.layout(cfg)),)
    # drawn again inside another program: equal to rounding
    assert np.all(norms < 1e-5)
