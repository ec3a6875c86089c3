"""The reader of where a phase came from (readers/phase_source.py, PR 38) on
the tiny step: the CPU trace has no device plane, so the device's events are
made here, one for every instruction of the tiny step's own compiled text that
would run as an operation, and joined to the program's text by instruction
name the way a chip's trace is."""

import json
import os
import re
import shutil

import pytest

import bm_util
from lib import harness, phase_table as pt
from readers import phase_ms, phase_source

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENTRY = "bert.train_step"
NEW_METRICS = ["step.inferred_phase_pct", "step.mixed_fusion_pct", "amp.pack_ms", "lm.pack_ms"]
INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?(\S+) = .*? ([a-z][a-z\-]*)\(")
NO_EVENT = ("parameter", "constant", "tuple", "get-tuple-element", "bitcast")


def _spec(metric):
    with open(os.path.join(BENCH_DIR, "metrics", metric + ".json")) as f:
        return json.load(f)


def _events(text):
    """One event a top-level instruction (fused computations run as their
    fusion), back to back, each as long as its place in the text says."""
    events, at, fused = [], 1000.0, False
    for line in text.splitlines():
        if line.endswith("{") and "=" not in line.split("(")[0]:
            fused = "fused_computation" in line.split("(")[0]
            continue
        m = INSTRUCTION.match(line)
        if m is None or fused or m.group(2) in NO_EVENT:
            continue
        name, kind = m.groups()
        duration = 100.0 + 7.0 * (len(events) % 13)
        events.append([f"%{name} = f32[] {kind}(", at, duration,
                       {"instr": re.sub(r"[.\d]+$", "", name), "kind": kind}])
        if kind not in ("while", "conditional", "call"):
            at += duration
    return events, at


@pytest.fixture(scope="module")
def tiny():
    """A ReadContext over made events of the tiny step the harness built,
    with the ledger that holds its compiled text in place."""
    from apex_tpu.observability import compilation
    from runners import train_example
    prev = compilation.set_ledger(compilation.CompilationLedger())
    release = train_example.Runner.release
    try:
        with pytest.MonkeyPatch.context() as patch:
            # the ledger hands out the text while the step lives, and keeps it
            patch.setattr(train_example.Runner, "release", lambda self: (
                compilation.get_ledger().compiled_text(ENTRY), release(self)))
            bm_util.run("bert-tiny.pretrain-32", seed=11, seconds=1.0)
        text = compilation.get_ledger().compiled_text(ENTRY)
        events, end = _events(text)
        ctx = harness.ReadContext(cell=None, facts={}, spans=[], trace={"planes": []},
                                  ops={0: events}, stretch=(0.0, end + 1.0), iterations=2,
                                  peaks=None)
        yield ctx, text
    finally:
        compilation.set_ledger(prev)
        pt._programs.pop(ENTRY, None)
        pt._attributed.clear()


def test_the_reader_reports_both_shares_on_the_tiny_step(tiny):
    ctx, _ = tiny
    inferred = phase_source.read(ctx, **_spec("step.inferred_phase_pct")["params"])
    mixed = phase_source.read(ctx, **_spec("step.mixed_fusion_pct")["params"])
    assert inferred["entry"] == mixed["entry"] == ENTRY        # lm.train_step is not in this ledger
    assert 0.0 <= inferred["value"] <= 100.0 and 0.0 <= mixed["value"] <= 100.0
    assert inferred["per_chip"] == {0: inferred["value"]}
    assert len(inferred["largest_inferred_ms"]) <= 12 and len(mixed["largest_mixed_ms"]) <= 12
    # a fusion of the backward pass and the pack's cast is in every amp O2 step
    assert mixed["value"] > 0 and mixed["mixed_fusions_in_text"] > 0
    for pair, ms in mixed["by_root_and_other_ms"].items():
        root, other = pair.split("|")
        assert root != other and 0 < ms <= mixed["mixed_fusion_ms"] + 1e-9


def test_time_by_source_sums_to_the_busy_time_and_none_is_unscoped(tiny):
    ctx, _ = tiny
    got = phase_source.read(ctx, **_spec("step.inferred_phase_pct")["params"])
    table = phase_ms.read(ctx, entry=ENTRY, unscoped=True)
    by_source = got["by_source_ms"]
    assert set(by_source) == {"own", "fused", "container", "sibling", "reader", "operand", "none"}
    assert sum(by_source.values()) == pytest.approx(table["busy_ms"][0], rel=1e-9)
    assert sum(by_source.values()) == pytest.approx(got["leaf_ops_ms"])
    assert by_source["none"] == pytest.approx(table["phase_ms"][0].get("unscoped", 0.0), abs=1e-12)
    assert by_source["own"] > 0
    inferred = sum(by_source[s] for s in phase_source.INFERRED)
    assert got["value"] == pytest.approx(100.0 * inferred / got["leaf_ops_ms"])
    assert sum(got["largest_inferred_ms"].values()) <= inferred + 1e-9
    assert table["not_in_text_ms"] == 0.0


def test_the_sources_are_the_programs_own(tiny):
    ctx, text = tiny
    from apex_tpu.observability import phases
    sources, program = phases.instruction_phase_sources(text), phases.instruction_phases(text)
    assert set(sources) == set(program)
    for name, source in sources.items():
        assert (source == "none") == (not program[name][0]), name
    rows = pt.rows_by_chip(ctx, ENTRY)[0]
    assert all(joined == pt.TEXT for *_, joined in rows)


def test_the_pack_metric_reads_the_accepted_reader_under_one_scope(tiny):
    ctx, _ = tiny
    spec, twin = _spec("amp.pack_ms"), _spec("lm.pack_ms")
    assert spec["reader"] == twin["reader"] == "phase_ms"
    assert spec["params"] == {"entry": ENTRY, "within": ["amp.pack"]}
    assert twin["params"] == {"entry": "lm.train_step", "within": ["amp.pack"]}
    pack = phase_ms.read(ctx, **spec["params"])
    step = phase_ms.read(ctx, **_spec("amp.step_ms")["params"])
    assert 0 < pack["value"] <= step["value"]
    assert set(pack["by_scope_ms"]) == {"amp.pack"}


def test_a_program_without_the_functions_reports_nothing(tiny, monkeypatch):
    ctx, _ = tiny
    from apex_tpu.observability import phases
    params = _spec("step.inferred_phase_pct")["params"]
    assert phase_source.read(ctx, entries=["an entry the ledger never saw"]) is None
    monkeypatch.delattr(phases, "fusion_phase_mix")            # the parent of PR 38
    assert phase_source.read(ctx, **params) is None
    assert phase_source.read(ctx, mixed=True, **params) is None
    monkeypatch.undo()
    empty = harness.ReadContext(cell=None, facts={}, spans=[], trace=None, ops={},
                                stretch=None, iterations=0, peaks=None)
    assert phase_source.read(empty, **params) is None


def test_the_new_metrics_are_found_by_name_alone(tmp_path):
    """The four metrics of PR 38 into the tiny tree by their files and entries:
    the CPU trace has no device plane, so each is left out of the line and
    nothing raises; every entry names the cells it is read in."""
    bench = tmp_path / "bench"
    shutil.copytree(bm_util.TINY, bench)
    man = bm_util.manifest()
    full = json.load(open(os.path.join(bm_util.ROOT, "BENCHMARK.json")))
    cells = [w["name"] for w in full["workloads"]]
    for name in NEW_METRICS:
        shutil.copy(os.path.join(BENCH_DIR, "metrics", name + ".json"), bench / "metrics")
        entry = dict(next(m for m in full["per_layer"] if m["name"] == name))
        assert entry["moves"] == "train.samples_per_s" and entry["better"] == "lower"
        assert set(entry.pop("workloads")) <= set(cells)
        man["per_layer"].append(entry)
    by_name = {m["name"]: m for m in full["per_layer"]}
    assert by_name["step.inferred_phase_pct"]["workloads"] == cells
    assert by_name["step.mixed_fusion_pct"]["workloads"] == cells
    assert by_name["amp.pack_ms"]["workloads"] == by_name["amp.step_ms"]["workloads"]
    assert (sorted(by_name["amp.pack_ms"]["workloads"] + by_name["lm.pack_ms"]["workloads"])
            == sorted(cells))
    result, _ = bm_util.run("bert-tiny.pretrain-32", seed=7, seconds=1.0, trace=True,
                            bench_dir=str(bench), man=man)
    assert result["correct"] is True
    assert not set(NEW_METRICS) & set(result["metrics"])
