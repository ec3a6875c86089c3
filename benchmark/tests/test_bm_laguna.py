"""The decoder cell's files on the CPU at the tiny configuration beside these
tests: the cell through ``runners/train_causal_lm`` is ``correct``, a step that
hands its state back is not, the reference one precision down falls outside
what the stated precision stays inside, the closed-form FLOPs agree with a
count by hand, and the new readers read a recorded-shape context and return
nothing where the program gave them nothing."""

import json
import os
import types

import numpy as np
import pytest

import bm_util

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "laguna-tiny.pretrain-lm-32"


def _manifest():
    man = bm_util.manifest()
    man["workloads"].append({"name": CELL, "config": "laguna-tiny", "traffic": "pretrain-lm-32",
                             "chips": 4})
    return man


def _tiny_cfg():
    return json.load(open(os.path.join(bm_util.TINY, "configs", "laguna-tiny.json")))


@pytest.fixture(scope="module")
def lm_run():
    return bm_util.run(CELL, seed=2**31 + 5, seconds=1.0, man=_manifest())


def test_decoder_cell_is_correct_on_four_virtual_devices(lm_run):
    result, lines = lm_run
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 3
    assert set(result["metrics"]) == {"train.samples_per_s", "setup_s"}
    compared = {l["compared"]: l for l in lines if "compared" in l}
    assert {"first_loss_gap", "loss_gap", "grad_norm_gap_mean", "update_norm_gap",
            "moe_dropped_assignments", "moe_held_shortfall", "replicas_differ",
            "traces_inside_window",
            "compiles_inside_window"} <= set(compared)
    assert compared["moe_dropped_assignments"]["value"] == 0
    assert 0 <= compared["moe_held_shortfall"]["value"] < compared["moe_held_shortfall"]["limit"]
    moe = next(l["moe"] for l in lines if "moe" in l)
    # 4 devices x 1 row x 32 tokens x 4 choices x 4 expert layers, a quarter of them held
    assert 0 < moe["moe_assignments_held"] < 4 * 32 * 4 * 4 and moe["moe_expert_load_max"] > 0


def test_traced_run_reports_set_up_and_the_program_counter():
    man = _manifest()
    man["per_layer"].append({"name": "moe.expert_load_max", "unit": "rows", "layer": "expert layer",
                             "moves": "train.samples_per_s", "workloads": [CELL]})
    bench = os.path.join(BENCH_DIR, "metrics", "moe.expert_load_max.json")
    tiny = os.path.join(bm_util.TINY, "metrics", "moe.expert_load_max.json")
    assert json.load(open(bench)) == json.load(open(tiny))
    result, _ = bm_util.run(CELL, seed=9, seconds=3.0, trace=True, man=man)
    assert result["correct"] is True
    assert set(result["metrics"]) == {"setup.init_s", "moe.expert_load_max"}
    assert result["metrics"]["moe.expert_load_max"]["value"] > 0


def test_step_that_returns_its_state_unchanged_is_not_correct():
    import jax
    from runners import train_causal_lm
    real = train_causal_lm.Runner._build

    def broken(self):
        run = real(self)
        step = run.train_step

        def stuck(state, batch):
            copy = jax.tree_util.tree_map(lambda x: x.copy(), state)   # the step donates
            return state, step(copy, batch)[1]
        run.train_step = stuck
        return run

    train_causal_lm.Runner._build = broken
    try:
        result, lines = bm_util.run(CELL, seed=4, seconds=0.5, man=_manifest())
    finally:
        train_causal_lm.Runner._build = real
    assert result["correct"] is False
    assert "update_norm_gap" in {l["compared"] for l in lines if "compared" in l and not l["ok"]}


def test_runner_refuses_another_generator_and_a_program_without_build(tmp_path):
    from lib import harness
    from runners import train_causal_lm
    cell = harness.load_cell(_manifest(), CELL, 1, 1.0, False, bm_util.ROOT, bm_util.TINY)
    other = types.SimpleNamespace(**{**vars(cell), "traffic": {"generator": "mlm_nsp_batch"}})
    with pytest.raises(ValueError, match="causal_lm_batch"):
        train_causal_lm.Runner(other, harness.Spans(), print)
    # the parent's example: a main() and no build()
    example = tmp_path / "examples" / "gpt"
    example.mkdir(parents=True)
    (example / "main_amp.py").write_text("def main():\n    pass\n")
    import dataclasses
    runner = train_causal_lm.Runner(dataclasses.replace(cell, root=str(tmp_path)),
                                    harness.Spans(), print)
    with pytest.raises(SystemExit, match="no build"):
        runner._build()


def test_collapsed_routing_and_limits_in_a_benchmark_configuration_are_refused():
    import dataclasses
    from lib import harness
    from runners import train_causal_lm
    man = json.load(open(os.path.join(bm_util.ROOT, "BENCHMARK.json")))
    cell = harness.load_cell(man, "laguna-xs2.pretrain-8k", 2**31 + 9, 1.0, False, bm_util.ROOT)
    runner = train_causal_lm.Runner(cell, harness.Spans(), print)
    runner.rows = cell.config["per_chip_batch"]
    # PERF.md section 6: 9641 held a step once the routing had collapsed, 33 123 balanced
    assert runner.held_shortfall(33123) == 0.0 and runner.held_shortfall(32529) < 0.01
    assert runner.held_shortfall(9641) > 0.7 > train_causal_lm.HELD_SHORTFALL_LIMIT
    assert runner.limits_override() == {}
    loose = dataclasses.replace(cell, config={**cell.config, "limits": {"loss_gap": 1.0}})
    with pytest.raises(ValueError, match="states no limits"):
        train_causal_lm.Runner(loose, harness.Spans(), print).limits_override()
    tiny = harness.load_cell(_manifest(), CELL, 1, 1.0, False, bm_util.ROOT, bm_util.TINY)
    assert train_causal_lm.Runner(tiny, harness.Spans(), print).limits_override() == _tiny_cfg()["limits"]


def test_generator_draws_full_rows_from_the_seed():
    from runners import train_causal_lm as t
    a, = t.causal_lm_batch({"seq_len": 16}, 2**31 + 7, 0, 3, 50)
    b, = t.causal_lm_batch({"seq_len": 16}, 2**31 + 7, 0, 3, 50)
    c, = t.causal_lm_batch({"seq_len": 16}, 2**31 + 7, 1, 3, 50)
    assert a.shape == (3, 16) and a.dtype == np.int32 and (a == b).all() and (a != c).any()
    assert a.min() >= 0 and a.max() < 50


def test_controls_fail_where_the_stated_precision_passes():
    """At a size a test can hold, relatively (the limits in references/laguna.py
    are the chip-size cell's): fp8-rounded matmuls move the first gradient at
    least three times as far as bf16 ones, and a bf16 parameter store and a
    stuck step each break the limit that is there for them."""
    import jax
    from apex_tpu import models
    from lib import weights
    from references import laguna as ref
    from runners.train_causal_lm import causal_lm_batch
    cfg = _tiny_cfg()
    limits = cfg["limits"]                  # the tiny configuration's own
    model = models.Laguna(models.LagunaConfig.from_dict(cfg))
    shapes = jax.eval_shape(lambda k: model.init(k)[0], jax.random.PRNGKey(0))
    for seed in (5, 6):
        params = weights.make_weights(shapes, seed=seed, std=0.02)
        batches = [causal_lm_batch({"seq_len": 32}, seed, i, 4, 64) for i in range(2)]
        want = ref.train(params, batches, cfg)
        sound = ref.compare(ref.train(params, batches, cfg, precision="bfloat16"), want)
        low = ref.compare(ref.train(params, batches, cfg, precision="fp8"), want)
        assert all(sound[k] < limits[k] for k in limits)
        assert low["grad_norm_gap_mean"] > 3 * sound["grad_norm_gap_mean"]
    again = ref.compare(ref.train(params, batches, cfg, block_rows=2), want)
    assert max(again[k] for k in limits) < 1e-4          # blocks only reorder the sums
    half = ref.compare(ref.train(params, batches, cfg, param_dtype="bfloat16"), want)
    assert half["update_norm_gap"] > limits["update_norm_gap"] > ref.LIMITS["update_norm_gap"]
    stuck = dict(want, update_norms=np.zeros_like(want["update_norms"]))
    assert ref.compare(stuck, want)["update_norm_gap"] > limits["update_norm_gap"]


def test_closed_form_flops_of_the_published_cut():
    from lib import laguna_flops as lf
    cfg = json.load(open(os.path.join(BENCH_DIR, "configs", "laguna-xs2.json")))
    assert lf.visible_pairs(8, None) == 36 and lf.visible_pairs(8, 3) == 1 + 2 + 3 + 5 * 3
    parts = lf.forward_flops_per_seq(cfg, 8192, 4 * 8192 * 8 * 16 / 256)
    per_token = {k: v / 8192 / 1e6 for k, v in parts.items()}
    # ISSUE 26's hand count, MFLOP a token: projections 344, scores 250, dense MLP 100.7, head 51.4
    assert abs(per_token["attention_projections"] - 344.5) < 1.0
    assert abs(per_token["attention_scores"] - 250.4) < 1.0
    assert abs(per_token["dense_mlp"] - 100.7) < 0.1 and abs(per_token["head"] - 51.4) < 0.1
    assert abs(per_token["router"] + per_token["shared_expert"] + per_token["routed_experts"]
               - 4 * 10.5) < 0.5
    fl, by = lf.flash_train_flops_bytes(cfg, 2, 8192, forward_calls=2.0)
    assert abs(fl / (11 / 2 * 2 * parts["attention_scores"]) - 1) < 1e-9
    assert by == (8 + 6 + 7) * 2 * (2 * 48 + 3 * 64) * 8192 * 128 * 2


def test_new_readers_return_nothing_without_a_trace_or_the_programs_spans():
    import importlib
    ctx = types.SimpleNamespace(cell=None, facts={}, spans=[], trace=None, ops={}, stretch=None,
                                iterations=0, peaks=None)
    for reader, params in (("lm_mfu", {}), ("flash_band_roofline", {}),
                           ("module_ms", {"entry": "lm.train_step", "modules": "self_attn"})):
        assert importlib.import_module("readers." + reader).read(ctx, **params) is None


def test_attention_ms_by_the_layer_kinds_of_the_configuration(monkeypatch):
    from lib import phase_table as pt
    from readers import module_ms
    cfg = json.load(open(os.path.join(BENCH_DIR, "configs", "laguna-xs2.json")))
    spec = json.load(open(os.path.join(BENCH_DIR, "metrics", "attn.step_ms.json")))
    row = lambda ms, module, bwd: (["%f = fusion()", 0.0, ms * 1e6, {}],
                                   ("lm.train_step", "model", module, "dot"), bwd, pt.TEXT)
    rows = [row(4.0, "layers/0/self_attn/q_proj", False), row(2.0, "layers/1/self_attn", False),
            row(6.0, "layers/3/self_attn/o_proj", True), row(8.0, "layers/4/self_attn", True),
            row(9.0, "layers/2/mlp/experts", False), row(1.0, "norm", False)]
    monkeypatch.setattr(pt, "rows_by_chip", lambda ctx, entry: {0: rows})
    ctx = types.SimpleNamespace(facts={"model": cfg}, iterations=2)
    got = module_ms.read(ctx, **spec["params"])
    assert got["value"] == 10.0
    assert got["by_module_kind_ms"] == {"full_attention.bwd": 4.0, "full_attention.fwd": 2.0,
                                        "sliding_attention.bwd": 3.0, "sliding_attention.fwd": 1.0}


def test_mfu_and_band_roofline_from_a_made_up_trace():
    from lib import laguna_flops as lf, peaks as pk
    from readers import flash_band_roofline, lm_mfu
    cfg = json.load(open(os.path.join(BENCH_DIR, "configs", "laguna-xs2.json")))
    peaks = pk.peaks_for("TPU v5 lite")
    kernel = lambda name, start, ns: [f"%{name}.1 = custom-call()", start, ns,
                                      {"instr": name, "kind": "custom-call"}]
    # two iterations of 1 s each: per layer two forwards (remat), one dq, one dkv
    events = []
    for it in range(2):
        for i in range(5):
            t0 = it * 1e9 + i * 1e8
            events += [kernel("flash_fwd", t0, 1e7), kernel("flash_fwd", t0 + 2e7, 1e7),
                       kernel("flash_dq", t0 + 4e7, 2e7), kernel("flash_dkv", t0 + 7e7, 2e7)]
    facts = {"rows_per_step": 2, "seq_len": 8192, "model": cfg, "tokens_per_step": 16384,
             "moe_assignments_held": 32768.0}
    ctx = types.SimpleNamespace(cell=types.SimpleNamespace(chips=1), facts=facts, spans=[],
                                trace={}, ops={0: events}, stretch=(0.0, 2e9), iterations=2,
                                peaks=peaks)
    got = flash_band_roofline.read(ctx)
    fl, _ = lf.flash_train_flops_bytes(cfg, 2, 8192, forward_calls=2.0)
    assert got["forward_calls_per_layer"] == 2.0 and abs(got["ms_per_step"] - 300.0) < 1e-6
    assert abs(got["value"] - 100.0 * fl / peaks["bf16_flops"] / 0.3) < 1e-6 and got["value"] < 100
    mfu = lm_mfu.read(ctx)
    assert abs(mfu["value"] - 100.0 * lf.train_flops_per_seq(cfg, 8192, 16384.0) * 2
               / peaks["bf16_flops"]) < 1e-6
    assert abs(sum(mfu["forward_share_by_part"].values()) - 1.0) < 1e-9
