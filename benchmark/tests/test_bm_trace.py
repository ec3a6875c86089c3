"""The trace reduction against a trimmed trace recorded on the chip (three
steps of bert-large.pretrain-512's program at batch 8, PR 23) and against
hand-made intervals."""

import os

import pytest

from lib import trace as tr

FIXTURE = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "fixtures", "v5e_bert_large_b8_3steps.json.gz")
SPANS = ["make_batch", "put_batch", "train_step", "block"]


@pytest.fixture(scope="module")
def chip():
    t = tr.load_trimmed(FIXTURE)
    spans = tr.host_spans(t, SPANS)            # the stretch: first span's start to last one's end
    return t, tr.device_ops(t)[0], (spans[0][1], max(e[1] + e[2] for e in spans))


def test_what_a_v5e_trace_holds(chip):
    t, ops, _ = chip
    assert [p["name"] for p in t["planes"]] == ["/device:TPU:0", "/host:CPU"]
    assert len(ops) == 25455
    kinds = {e[3].get("kind") for e in ops}
    assert {"fusion", "custom-call", "conditional", "copy-start", "copy-done"} <= kinds
    assert tr.parse_instr("%flash_fwd.3 = (bf16[8,16,512,64]{3,2,1,0:T(8,128)(2,1)}) "
                          "custom-call(bf16[8] %x), custom_call_target=\"tpu_custom_call\"") \
        == {"instr": "flash_fwd", "kind": "custom-call"}


def test_busy_idle_and_kernel_sums_on_the_chip_trace(chip):
    t, ops, (lo, hi) = chip
    stretch_ms = (hi - lo) / 1e6
    busy_ms = tr.busy(ops, lo, hi) / 1e6
    assert stretch_ms == pytest.approx(442.06, abs=0.01)
    assert busy_ms == pytest.approx(430.50, abs=0.01)        # idle 2.6 %
    flash_s, flash_calls = tr.kernel_seconds(ops, "flash_(fwd|dq|dkv)", lo, hi)
    adam_s, adam_calls = tr.kernel_seconds(ops, "_adam_flat", lo, hi)
    assert (flash_calls, adam_calls) == (216, 3)              # 3 steps x 24 layers x 3 kernels
    assert flash_s * 1e3 / 3 == pytest.approx(15.33, abs=0.01)
    assert adam_s * 1e3 / 3 == pytest.approx(15.22, abs=0.01)
    # a container's time is its body's: not counted twice among the leaves
    leaves_ms = sum(e[2] for e in tr.leaf_ops(ops)) / 1e6
    assert leaves_ms <= busy_ms * 1.02
    labels = [name for name, _ in tr.top_ops(ops, lo, hi, 10)]
    assert "_adam_flat" in labels and "flash_fwd" in labels
    assert tr.exposed_collective_seconds(ops, tr.async_spans(t, 0), lo, hi) == 0.0


def test_idle_gaps_are_charged_to_harness_spans(chip):
    t, ops, (lo, hi) = chip
    gaps = dict(tr.idle_gaps(ops, tr.host_spans(t, SPANS), lo, hi))
    assert sum(gaps.values()) * 1e3 == pytest.approx(442.06 - 430.50, abs=0.02)
    assert set(gaps) <= set(SPANS) | {"outside_harness_spans"}


def test_interval_arithmetic():
    assert tr.merge([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 11)]) == [(0, 2), (3, 5)]
    assert tr.clip([(0, 4), (6, 9)], 2, 7) == [(2, 4), (6, 7)]


def test_collective_exposure_counts_only_uncovered_time():
    ev = lambda instr, kind, s, d: [instr, float(s), float(d), {"instr": instr, "kind": kind}]
    ops = [ev("fusion", "fusion", 0, 10),
           ev("all-reduce", "all-reduce", 10, 6),              # blocking: all 6 exposed
           ev("all-reduce-start", "all-reduce-start", 20, 1),
           ev("fusion", "fusion", 21, 4),
           ev("all-reduce-done", "all-reduce-done", 25, 5)]    # waits 5 with nothing else running
    async_line = [ev("all-reduce-start", "all-reduce-start", 20, 10)]
    exposed = tr.exposed_collective_seconds(ops, async_line, 0, 40) * 1e9
    assert exposed == pytest.approx(6 + 1 + 5)                  # 21..25 is hidden behind the fusion


def test_a_container_does_not_hide_the_gaps_in_its_body():
    ev = lambda instr, kind, s, d: [instr, float(s), float(d), {"instr": instr, "kind": kind}]
    ops = [ev("while", "while", 0, 100),                       # spans its body, gaps included
           ev("fusion", "fusion", 0, 30),
           ev("gather", "gather", 50, 40)]
    assert tr.busy(ops, 0, 100) == 70
    spans = [["engine.step", 0.0, 100.0, {}]]
    assert tr.idle_gaps(ops, spans, 0, 100) == [["engine.step", pytest.approx(30e-9)]]
