"""The per-phase readers against a second trimmed trace recorded on the chip
after the program named its phases (three steps of bert-large.pretrain-512 at
batch 8, PR 24, with the phase the program's ``instruction_phases`` gave each
traced instruction), and the new metrics added to the tiny tree by files and
entries alone."""

import gzip
import json
import os
import shutil

import pytest

import bm_util
from lib import harness, phase_table as pt, trace as tr
from readers import kernel_ms, phase_ms

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(BENCH_DIR, "fixtures", "v5e_bert_large_b8_3steps_phases.json.gz")
ENTRY = "bert.train_step"
NEW_METRICS = ["amp.step_ms", "amp.repack_ms", "ddp.step_ms", "model.fwd_ms", "model.bwd_ms",
               "step.unscoped_pct", "setup.import_s", "setup.model_init_s",
               "setup.step_trace_s", "setup.step_load_s"]
SETUP_METRICS = [m for m in NEW_METRICS if m.startswith("setup.")]


def _params(metric):
    with open(os.path.join(BENCH_DIR, "metrics", metric + ".json")) as f:
        return json.load(f)["params"]


@pytest.fixture(scope="module")
def chip():
    """A ReadContext over the recorded trace; the phases the program gave stand
    in for its ledger."""
    with gzip.open(FIXTURE, "rt") as f:
        recorded = json.load(f)
    trace = {"planes": recorded["planes"]}
    ops = tr.device_ops(trace)
    marks = tr.host_spans(trace, ["make_batch"])
    stretch = (marks[0][1], max(e[1] + e[2] for e in tr.host_spans(trace)))
    pt._programs[ENTRY] = {name: (tuple(path), backward)
                           for name, (path, backward) in recorded["phases"].items()}
    ctx = harness.ReadContext(cell=None, facts={}, spans=[], trace=trace, ops=ops,
                              stretch=stretch, iterations=len(marks), peaks=None)
    yield ctx
    pt._programs.pop(ENTRY, None)


def test_the_trace_and_the_program_name_the_same_instructions(chip):
    phases = pt.program_phases(ENTRY)
    leaves = tr.leaf_ops(chip.ops[0])
    named = [e for e in leaves if pt.instruction_name(e) in phases]
    assert chip.iterations == 3 and len(leaves) > 20000
    assert len(named) == len(leaves)


def test_phases_partition_the_leaf_operations(chip):
    got = phase_ms.read(chip, **_params("step.unscoped_pct"))
    table, busy = got["phase_ms"][0], got["busy_ms"][0]
    assert sum(table.values()) == pytest.approx(got["phase_sum_ms"][0])
    assert got["phase_sum_ms"][0] == pytest.approx(busy, rel=1e-3)
    assert got["value"] == pytest.approx(100.0 * table["unscoped"] / sum(table.values()))
    assert got["value"] <= 10.0
    # the join by instruction name found every operation in the program's text
    assert got["not_in_text_ms"] == 0.0 and got["by_container_ms"] == 0.0
    assert {"amp.update", "model", "model.bwd", "loss", "amp.pack"} <= set(table)


def test_amp_step_holds_the_kernels_and_the_repacking(chip):
    step = phase_ms.read(chip, **_params("amp.step_ms"))
    repack = phase_ms.read(chip, **_params("amp.repack_ms"))
    kernels = kernel_ms.read(chip, pattern="_adam_flat|_scale_flat")
    assert kernels["calls_per_iteration"] == 2
    assert step["value"] >= kernels["value"]
    assert repack["value"] < step["value"]
    assert step["value"] >= repack["value"] + kernels["value"] - 1e-6
    assert not any(k in ("_adam_flat", "_scale_flat") for k in repack["by_op_ms"])


def test_model_time_splits_by_direction_and_module_kind(chip):
    fwd = phase_ms.read(chip, **_params("model.fwd_ms"))
    bwd = phase_ms.read(chip, **_params("model.bwd_ms"))
    table = phase_ms.read(chip, **_params("step.unscoped_pct"))["phase_ms"][0]
    assert fwd["value"] == pytest.approx(table["model"] + table["loss"])
    assert bwd["value"] == pytest.approx(table["model.bwd"] + table["loss.bwd"])
    assert bwd["value"] > fwd["value"] > 0
    kinds = {"attention", "intermediate", "output", "LayerNorm", "embeddings", "heads", "loss"}
    assert kinds <= set(fwd["by_module_kind_ms"]) | {"other"}
    assert sum(fwd["by_module_kind_ms"].values()) == pytest.approx(fwd["value"])
    assert fwd["by_module_kind_ms"].get("other", 0.0) < 0.02 * fwd["value"]


def test_no_ddp_time_on_one_chip_and_nothing_without_the_program(chip):
    assert phase_ms.read(chip, **_params("ddp.step_ms"))["value"] == pytest.approx(0.0, abs=0.5)
    # the parent of PR 24 has no ledger method and no phases module: nothing is read
    assert pt.program_phases("an entry the ledger never saw") is None
    assert phase_ms.read(chip, entry="an entry the ledger never saw", within=["model"]) is None


def test_an_instruction_the_text_lacks_takes_its_container_s_phase():
    ev = lambda name, kind, s, d: [f"%{name} = f32[] {kind}(", float(s), float(d),
                                   {"instr": name.split(".")[0], "kind": kind}]
    phases = {"conditional.1": (("amp.update",), False), "fusion.2": (("model",), True)}
    rows = pt.attribute([ev("conditional.1", "conditional", 0, 50), ev("copy.9", "copy", 10, 5),
                         ev("fusion.2", "fusion", 60, 5), ev("copy.10", "copy", 70, 5)],
                        phases, 0, 100)
    assert [(e[3]["instr"], path, back, source) for e, path, back, source in rows] == [
        ("copy", ("amp.update",), False, pt.CONTAINER), ("fusion", ("model",), True, pt.TEXT),
        ("copy", (), False, pt.NOWHERE)]
    assert pt.table(rows, 1) == {"amp.update": 5e-6, "model.bwd": 5e-6, "unscoped": 5e-6}
    assert pt.matches(("amp.update", "optim.adam"), ["optim.*"])
    assert not pt.matches(("amp.update",), ["optim.*", "amp.pack"])


def test_a_stretch_without_operations_reports_nothing():
    op = ["%fusion.1 = f32[] fusion(", 10.0, 5.0, {"instr": "fusion", "kind": "fusion"}]
    ctx = harness.ReadContext(cell=None, facts={}, spans=[], trace={"planes": []}, ops={0: [op]},
                              stretch=(1000.0, 2000.0), iterations=1, peaks=None)
    pt._programs["t.empty"] = {"fusion.1": (("model",), False)}
    try:
        assert phase_ms.read(ctx, entry="t.empty", unscoped=True) is None
    finally:
        pt._programs.pop("t.empty")


def test_new_metrics_are_added_by_files_and_entries_alone(tmp_path):
    """Each metric of PR 24 into the tiny tree, the way a later PR adds one:
    its ``metrics/<name>.json`` and a ``per_layer`` entry.  The CPU trace has no
    device plane, so the device metrics are left out and the set-up ones read."""
    bench = tmp_path / "bench"
    shutil.copytree(bm_util.TINY, bench)
    man = bm_util.manifest()
    full = json.load(open(os.path.join(bm_util.ROOT, "BENCHMARK.json")))
    for name in NEW_METRICS + ["setup.compile_s"]:
        shutil.copy(os.path.join(BENCH_DIR, "metrics", name + ".json"), bench / "metrics")
        entry = dict(next(m for m in full["per_layer"] if m["name"] == name))
        entry.pop("workloads", None)
        man["per_layer"].append(entry)
    # one run a process in the benchmark proper; here earlier tests' builds of the
    # same ledger entry would add their seconds to this run's
    from apex_tpu.observability import compilation
    prev = compilation.set_ledger(compilation.CompilationLedger())
    try:
        result, lines = bm_util.run("bert-tiny.pretrain-32", seed=7, seconds=3.0, trace=True,
                                    bench_dir=str(bench), man=man)
    finally:
        compilation.set_ledger(prev)
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert result["correct"] is True
    assert set(got) == set(SETUP_METRICS) | {"setup.init_s", "setup.compile_s"}
    setup_s = next(l["setup_s"] for l in lines if "setup_s" in l)
    assert 0 < got["setup.step_trace_s"] + got["setup.step_load_s"] <= got["setup.compile_s"]
    assert 0 < got["setup.model_init_s"] <= got["setup.init_s"]
    assert got["setup.import_s"] > 0
    assert sum(got[m] for m in SETUP_METRICS) <= setup_s
    spans = next(l for l in lines if l.get("metric") == "setup.model_init_s")["spans"]
    assert list(spans) == ["build.model_init", "build.place_params", "build.optimizer_init"]
    assert all(0 <= s["begin_s"] <= setup_s for s in spans.values())
    stages = next(l for l in lines if l.get("metric") == "setup.step_load_s")
    assert {"trace_s", "lower_s", "cache_load_s", "backend_compile_s"} <= set(stages)
