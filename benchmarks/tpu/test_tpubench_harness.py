"""The harness is driven by data: a configuration, a mix and a metric are
files found by name.  CPU only; nothing here touches a TPU."""
import dataclasses
import hashlib
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import harness
import run

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def spec():
    return json.loads((CHECKOUT / "BENCHMARK.json").read_text())


def digest(root: Path) -> dict:
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


def cpu_devices():
    import jax
    return jax.devices("cpu")[:1]


def test_every_name_in_the_benchmark_has_its_files():
    s = spec()
    root = CHECKOUT / s["paths"][0]
    for c in s["configs"]:
        assert NAME.match(c["name"]) and (CHECKOUT / c["file"]).is_file()
        assert json.loads((CHECKOUT / c["file"]).read_text())["name"] \
            == c["name"]
    for w in s["workloads"]:
        assert NAME.match(w["name"])
        mix = json.loads((root / "mixes" / f"{w['traffic']}.json")
                         .read_text())
        assert (root / "drivers" / f"{mix['driver']}.py").is_file()
        assert w["chips"] == 1
    e2e = {m["name"] for m in s["end_to_end"]}
    for m in s["end_to_end"] + s["per_layer"]:
        assert NAME.match(m["name"])
        assert callable(harness.load_module(
            root / "metrics" / f"{m['name']}.py").read)
    for m in s["per_layer"]:
        assert m["moves"] in e2e
        for w in m["workloads"]:
            moved = next(x for x in s["end_to_end"] if x["name"] == m["moves"])
            assert w in moved.get("workloads", [w])


def test_new_config_mix_and_metric_need_only_new_files(tmp_path):
    checkout = tmp_path / "checkout"
    root = checkout / "benchmarks" / "tpu"
    shutil.copytree(HERE, root, ignore=shutil.ignore_patterns(
        "__pycache__", "testdata", "test_*.py", "conftest.py"))
    shutil.copy(CHECKOUT / "BENCHMARK.json", checkout / "BENCHMARK.json")
    before = digest(root)

    config = json.loads((root / "configs" / "internvl2.json").read_text())
    config.update(name="tinylm", hidden_size=64, num_attention_heads=4,
                  num_key_value_heads=2, intermediate_size=160,
                  vocab_size=256, num_hidden_layers=2)
    config["vision"] = {"num_prefix_tokens": 8, "patch_embed_dim": 32}
    config["train"]["text_len"] = 16
    (root / "configs" / "tinylm.json").write_text(json.dumps(config))
    mix = json.loads((root / "mixes" / "train_detached.json").read_text())
    mix["warmup_steps"] = 3
    (root / "mixes" / "train_short_warmup.json").write_text(json.dumps(mix))
    (root / "metrics" / "steps_done.py").write_text(
        "def read(run):\n    return float(run.counters['attempted'])\n")
    s = spec()
    s["configs"].append({"name": "tinylm", "source": "test",
                         "file": "benchmarks/tpu/configs/tinylm.json",
                         "reduced": [], "why": "test"})
    s["workloads"].append({"name": "tinylm.train_short_warmup",
                           "config": "tinylm",
                           "traffic": "train_short_warmup",
                           "chips": 1, "why": "test"})
    for m in s["end_to_end"]:
        if m["name"] == "step_ms":
            m["workloads"].append("tinylm.train_short_warmup")
    s["end_to_end"].append({"name": "steps_done", "unit": "count",
                            "better": "higher", "bound": 0.01,
                            "source": "host_clock",
                            "workloads": ["tinylm.train_short_warmup"]})
    (checkout / "BENCHMARK.json").write_text(json.dumps(s))

    after = digest(root)
    assert all(after[p] == h for p, h in before.items())
    cell = harness.find_cell("tinylm.train_short_warmup",
                             checkout / "BENCHMARK.json")
    assert cell.config["hidden_size"] == 64
    assert cell.mix["warmup_steps"] == 3
    assert {m["name"] for m in cell.end_to_end} == {
        "step_ms", "setup_s", "steps_done"}
    assert "train_mfu" not in {m["name"] for m in cell.per_layer}
    result, checks = run.measure(cell, 2**33 + 1, 0.5, False, cpu_devices())
    assert result["correct"], checks
    assert result["metrics"]["steps_done"]["value"] >= 1
    assert result["metrics"]["step_ms"]["value"] > 0


def test_a_run_without_a_tpu_prints_no_result():
    proc = subprocess.run(
        [sys.executable, str(HERE / "run.py"), "--workload",
         "internvl2.train_detached", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=300,
        cwd=CHECKOUT, env={**os.environ,
                           "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not proc.stdout.strip().startswith("{")
    assert "{" not in proc.stdout


def test_window_clock_counts_units_and_stops_the_trace():
    stopped = []
    clock = run.WindowClock(0.0, 0.0, on_trace_end=lambda: stopped.append(1))
    clock.start()
    assert clock.tick()
    assert clock.tick()
    assert stopped == [1] and len(clock.marks) == 2
    assert clock.elapsed == clock.marks[-1] - clock.t0 >= 0


def test_percentile_is_linear_between_order_statistics():
    xs = list(range(1, 101))
    assert harness.percentile(xs, 95) == pytest.approx(95.05)
    assert harness.percentile([3.0], 95) == 3.0


def test_checks_fail_on_nan_and_print_last(capsys):
    checks = [harness.Check("a", 0.5, 1.0), harness.Check("b", float("nan"),
                                                          1.0)]
    assert checks[0].ok and not checks[1].ok
    harness.emit({"correct": False}, checks)
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks" and line["checks"]["a"]["limit"] == 1.0
    assert err.strip().splitlines()[-1].startswith("check b:")


def test_cell_lookup_rejects_an_unknown_workload():
    with pytest.raises(KeyError, match="unknown workload"):
        harness.find_cell("nope.nothing")
    cell = harness.find_cell("internvl2.train_detached")
    assert dataclasses.is_dataclass(cell) and cell.chips == 1
