"""The harness end to end on the CPU at the tiny configurations beside these
tests, through its Python functions; ``run.py`` refusing anything but a TPU;
the timed path broken underneath coming out as not correct; a throw-away cell
added by new files and entries alone."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import bm_util

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _lines(lines, key):
    return [l for l in lines if key in l]


def _not_ok(lines):
    return [l for l in _lines(lines, "compared") if not l["ok"]]


@pytest.fixture(scope="module")
def train_run():
    return bm_util.run("bert-tiny.pretrain-32", seed=2**31 + 11, seconds=1.0)


def test_training_cell_on_four_virtual_devices(train_run):
    result, lines = train_run
    assert set(result) == {"correct", "attempted", "failed", "metrics", "device"}
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 3
    assert set(result["metrics"]) == {"train.samples_per_s", "setup_s"}
    assert result["device"]["platform"] == "cpu" and result["device"]["count"] == 4
    compared = {l["compared"]: l for l in _lines(lines, "compared")}
    assert {"loss_gap", "grad_norm_gap_mean", "update_norm_gap", "replicas_differ",
            "traces_inside_window", "compiles_inside_window"} <= set(compared)
    assert all("limit" in l and "value" in l for l in compared.values())
    assert _lines(lines, "inside_window")[0]["inside_window"] == {"traces": 0, "backend_compiles": 0}


def test_traced_run_reports_per_layer_metrics_only():
    result, _ = bm_util.run("bert-tiny.pretrain-32", seed=3, seconds=3.0, trace=True)
    # no device plane in a CPU trace: the idle reader finds nothing and is left out
    assert set(result["metrics"]) == {"setup.init_s"}
    assert "breakdown" not in result and result["correct"] is True


def test_step_that_returns_its_state_unchanged_is_not_correct():
    """The timed path broken underneath: the step computes its metrics and hands
    the state back as it got it."""
    import jax
    from runners import train_example
    real = train_example.Runner._build

    def broken(self):
        run = real(self)
        step = run.train_step

        def stuck(state, batch):
            copy = jax.tree_util.tree_map(lambda x: x.copy(), state)   # the step donates
            return state, step(copy, batch)[1]
        run.train_step = stuck
        return run

    train_example.Runner._build = broken
    try:
        result, lines = bm_util.run("bert-tiny.pretrain-32", seed=4, seconds=0.5)
    finally:
        train_example.Runner._build = real
    assert result["correct"] is False
    assert "update_norm_gap" in {l["compared"] for l in _not_ok(lines)}


def test_a_cell_is_added_by_files_and_entries_alone(tmp_path):
    """A later PR adds a configuration, a mix, a per-layer metric and a cell
    without editing a file that is there."""
    bench = tmp_path / "bench"
    shutil.copytree(bm_util.TINY, bench)
    cfg = json.load(open(bench / "configs" / "bert-tiny.json"))
    cfg["name"], cfg["per_chip_batch"] = "bert-tiny-b2", 2
    json.dump(cfg, open(bench / "configs" / "bert-tiny-b2.json", "w"))
    mix = json.load(open(bench / "traffic" / "pretrain-32.json"))
    mix["params"]["seq_len"] = 16
    json.dump(mix, open(bench / "traffic" / "pretrain-16.json", "w"))
    json.dump({"name": "train.steps", "reader": "fact", "params": {"key": "steps"}},
              open(bench / "metrics" / "train.steps.json", "w"))
    man = bm_util.manifest()
    man["workloads"].append({"name": "bert-tiny-b2.pretrain-16", "config": "bert-tiny-b2",
                             "traffic": "pretrain-16", "chips": 4})
    man["per_layer"].append({"name": "train.steps", "unit": "steps", "layer": "entry points",
                             "moves": "train.samples_per_s",
                             "workloads": ["bert-tiny-b2.pretrain-16"]})
    result, _ = bm_util.run("bert-tiny-b2.pretrain-16", seed=1, seconds=1.0, trace=True,
                            bench_dir=str(bench), man=man)
    assert result["correct"] is True
    assert result["metrics"]["train.steps"]["value"] == result["attempted"] > 0


def _run_py(cwd, extra_env=None):
    env = {k: v for k, v in os.environ.items() if not k.startswith(("XLA_", "JAX_"))}
    env.update({"JAX_PLATFORMS": "cpu", **(extra_env or {})})
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmark", "run.py"), "--workload",
         "bert-large.pretrain-512", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_run_py_refuses_a_cpu_before_building_anything():
    proc = _run_py(ROOT)
    assert proc.returncode != 0
    assert "needs 1 TPU chip" in proc.stderr and "Nothing was built" in proc.stderr
    assert '"correct"' not in proc.stdout


def test_run_py_refuses_a_directory_with_only_the_benchmark(tmp_path):
    shutil.copytree(BENCH_DIR, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    proc = _run_py(str(tmp_path))
    assert proc.returncode != 0 and '"correct"' not in proc.stdout


def test_manifest_names_files_that_exist():
    man = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    e2e = {m["name"] for m in man["end_to_end"]}
    cells = {w["name"] for w in man["workloads"]}
    for c in man["configs"]:
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        for mod in ("runners/" + cfg["runner"], "references/" + cfg["reference"]):
            assert os.path.exists(os.path.join(BENCH_DIR, mod + ".py"))
    for w in man["workloads"]:
        assert os.path.exists(os.path.join(BENCH_DIR, "traffic", w["traffic"] + ".json"))
        assert len(w["why"]) <= 200
    for m in man["per_layer"]:
        spec = json.load(open(os.path.join(BENCH_DIR, "metrics", m["name"] + ".json")))
        assert os.path.exists(os.path.join(BENCH_DIR, "readers", spec["reader"] + ".py"))
        assert m["moves"] in e2e and set(m.get("workloads", cells)) <= cells
