"""The ``lfm2`` cell's files on the CPU at the tiny configuration beside these
tests: the cell through ``runners/train_causal_lm`` and ``references/lfm2`` is
``correct``, the reference one precision down and a step that leaves the bias
out are not, the cut keeps every published width, the closed-form FLOPs agree
with ISSUE 33's count by hand and stay under the peak, every twin metric file
equals its twin's parameters, and the routing tool hands the reference a choice
of experts."""

import importlib.util
import json
import os
import types

import numpy as np

import bm_util

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "lfm2-tiny.pretrain-lm-32"
PUBLISHED = {"conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 7168,
             "max_position_embeddings": 128000, "model_type": "lfm2_moe",
             "moe_intermediate_size": 1792, "norm_eps": 1e-05, "norm_topk_prob": True,
             "num_attention_heads": 32, "num_experts_per_tok": 4, "num_key_value_heads": 8,
             "rope_theta": 1000000, "routed_scaling_factor": 1, "use_expert_bias": True}


def _manifest():
    man = bm_util.manifest()
    man["workloads"].append({"name": CELL, "config": "lfm2-tiny", "traffic": "pretrain-lm-32",
                             "chips": 4})
    return man


def _tiny_cfg():
    return json.load(open(os.path.join(bm_util.TINY, "configs", "lfm2-tiny.json")))


def _cut():
    return json.load(open(os.path.join(BENCH_DIR, "configs", "lfm2-8b-a1b.json")))


def test_lfm2_cell_is_correct_on_four_virtual_devices():
    result, lines = bm_util.run(CELL, seed=2**31 + 5, seconds=1.0, man=_manifest())
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 3
    compared = {l["compared"]: l for l in lines if "compared" in l}
    assert {"loss_gap", "grad_diff_mean", "update_norm_gap",
            "moe_dropped_assignments", "moe_held_shortfall", "replicas_differ"} <= set(compared)
    assert compared["moe_dropped_assignments"]["value"] == 0
    moe = next(l["moe"] for l in lines if "moe" in l)
    # 4 devices x 1 row x 32 tokens x 4 choices x 4 expert layers, half of them held
    assert 0 < moe["moe_assignments_held"] < 4 * 32 * 4 * 4 and moe["moe_expert_load_max"] > 0


def test_a_stated_window_steps_ends_the_window_and_is_where_the_shortfall_is_read(tmp_path):
    """A copy of the tiny configuration with ``window_steps``: whatever
    ``--seconds`` is the window runs that many steps, the series line holds one
    number a step from the first of set-up's, and ``moe_held_shortfall`` is
    read at the counted last step."""
    import shutil
    from runners import train_causal_lm
    bench = tmp_path / "bench"
    shutil.copytree(bm_util.TINY, bench)
    cfg = dict(_tiny_cfg(), window_steps=5)
    json.dump(cfg, open(bench / "configs" / "lfm2-tiny.json", "w"))
    runs = [bm_util.run(CELL, seed=9, seconds=s, man=_manifest(), bench_dir=str(bench))
            for s in (30.0, 45.0)]
    for result, lines in runs:
        assert result["correct"] is True and result["attempted"] == 5
        ended = next(l for l in lines if "window_ended_by" in l)
        assert (ended["window_ended_by"], ended["at_step"], ended["window_steps"]) == ("count", 5, 5)
        series = next(l for l in lines if "by_step" in l)
        held = series["by_step"]["moe_assignments_held"]
        assert len(held) == len(series["by_step"]["moe_expert_load_max"]) == series["setup_steps"] + 5
        last = next(l["last_step"] for l in lines if "last_step" in l and "moe" in l)
        assert last["moe_assignments_held"] == held[-1]
        compared = next(l for l in lines if l.get("compared") == "moe_held_shortfall")
        expected = 4 * 32 * 4 * 4 * 8 / 16     # tokens x choices x sparse layers x held / published
        assert compared["value"] == max(0.0, 1.0 - held[-1] / expected)
        assert compared["limit"] == train_causal_lm.HELD_SHORTFALL_LIMIT == 0.2
    # the same steps of the same run on both: the same series, whatever --seconds
    assert ([l for l in runs[0][1] if "by_step" in l] == [l for l in runs[1][1] if "by_step" in l])


def test_controls_and_a_model_without_its_bias_fail_where_the_stated_precision_passes():
    """At a size a test can hold, relatively (the limits in references/lfm2.py
    are the chip-size cell's): fp8-rounded matmuls move the first gradient at
    least three times as far as bf16 ones, a bf16 parameter store breaks the
    limit that is there for it, and the same weights with the selection bias
    left out of the choice are another model (the seed draws the bias so)."""
    import jax
    from apex_tpu import models
    from lib import weights
    from references import lfm2 as ref
    from runners.train_causal_lm import causal_lm_batch
    cfg = _tiny_cfg()
    limits = cfg["limits"]                  # the tiny configuration's own
    model = models.Laguna(models.LagunaConfig.from_dict(cfg))
    shapes = jax.eval_shape(lambda k: model.init(k)[0], jax.random.PRNGKey(0))
    for seed in (5, 6):
        params = weights.make_weights(shapes, seed=seed, std=cfg["init_std"])
        batches = [causal_lm_batch({"seq_len": 32}, seed, i, 4, 64) for i in range(2)]
        want = ref.train(params, batches, cfg)
        sound = ref.compare(ref.train(params, batches, cfg, precision="bfloat16"), want)
        low = ref.compare(ref.train(params, batches, cfg, precision="fp8"), want)
        assert all(sound[k] < limits[k] for k in limits), sound
        assert low["grad_diff_mean"] > 3 * sound["grad_diff_mean"]
    again = ref.compare(ref.train(params, batches, cfg, block_rows=2), want)
    assert max(again[k] for k in limits) < 1e-4          # blocks only reorder the sums
    half = ref.compare(ref.train(params, batches, cfg, param_dtype="bfloat16"), want)
    assert half["update_norm_gap"] > limits["update_norm_gap"] > ref.LIMITS["update_norm_gap"]
    bias = params["layers"]["2"]["mlp"]["expert_bias"]
    assert float(abs(bias).max()) > 0                   # drawn from the seed, not zero
    unbiased = jax.tree_util.tree_map_with_path(
        lambda path, x: x * 0 if "expert_bias" in jax.tree_util.keystr(path) else x, params)
    without = ref.compare(ref.train(unbiased, batches, cfg), want)
    assert without["grad_diff_mean"] > limits["grad_diff_mean"]


def test_reference_imports_nothing_of_the_program():
    src = open(os.path.join(BENCH_DIR, "references", "lfm2.py")).read()
    assert "import apex_tpu" not in src and "from apex_tpu" not in src
    assert "Precision.HIGHEST" in open(os.path.join(BENCH_DIR, "references", "_precision.py")).read()


def test_the_cut_keeps_every_published_width_and_the_readers_keys():
    cfg = _cut()
    for key, value in PUBLISHED.items():
        assert cfg[key] == value, key
    assert cfg["head_dim"] * cfg["num_attention_heads"] == cfg["hidden_size"]
    assert cfg["layer_types"] == ["conv", "full_attention", "conv", "conv", "conv"]
    assert cfg["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"]) == (5, 1)
    assert cfg["num_experts"] == 8 and cfg["num_experts_published"] == 32
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types", "num_dense_layers", "num_experts",
                              "vocab_size"]
    man = json.load(open(os.path.join(bm_util.ROOT, "BENCHMARK.json")))
    entry = next(c for c in man["configs"] if c["name"] == "lfm2-8b-a1b")
    assert entry["reduced"] == cfg["reduced"]
    assert cfg["vocab_size"] * 4 == cfg["published"]["vocab_size"] == 65536
    assert (cfg["published"]["num_hidden_layers"], cfg["published"]["num_dense_layers"],
            cfg["published"]["num_experts"]) == (24, 2, 32)
    # stated as derived, so that the accepted runner and readers read the file unedited
    assert cfg["rms_norm_eps"] == cfg["norm_eps"] and cfg["moe_routed_scaling_factor"] == 1.0
    assert cfg["shared_expert_intermediate_size"] == 0 and cfg["gating"] is False
    assert cfg["rope_parameters"] == {"full_attention": {"rope_type": "default",
                                                         "rope_theta": 1000000}}
    assert set(cfg["assumed"]["planned_bytes"]) >= {"remat_none", "remat_dots", "remat_nothing"}
    assert "limits" not in cfg and len(entry["source"]) < 200
    assert cfg["per_chip_batch"] * 8192 * cfg["num_experts_per_tok"] // 32 == 2048   # rows an expert


def test_closed_form_flops_of_the_published_cut():
    from lib import lfm2_flops as lf, peaks as pk
    cfg = _cut()
    parts = lf.forward_flops_per_seq(cfg, 8192, 4 * 8192 * 4 * 8 / 32)
    per_token = {k: v / 8192 / 1e6 for k, v in parts.items()}
    # ISSUE 33's hand count, MFLOP a token: the four convolution operators' projections 134,
    # the routed experts held 88, the dense layer's MLP 88, the head over a quarter of the
    # vocabulary 67, attention's one layer 55 (projections 21, scores 34 at 8192 tokens)
    assert abs(per_token["conv_projections"] - 4 * 2 * 2048 * 8192 / 1e6) < 1e-6
    assert abs(per_token["conv_projections"] - 134.2) < 0.1
    assert abs(per_token["routed_experts"] - 88.1) < 0.1 and abs(per_token["dense_mlp"] - 88.1) < 0.1
    assert abs(per_token["head"] - 67.1) < 0.1
    assert abs(per_token["attention_projections"] - 21.0) < 0.1
    assert abs(per_token["attention_scores"] - 33.6) < 0.1
    # the taps: 4 layers x 2048 channels x (B * z, three multiply-adds, C * c)
    assert abs(per_token["conv_taps"] - 4 * 2048 * 8 / 1e6) < 1e-9 and per_token["router"] < 0.6
    step = 3.0 * sum(parts.values()) * 2
    assert abs(step / 1e12 - 21.3) < 0.2                 # 21.3 TFLOP a step of 16 384 tokens
    # attention is counted in the one full_attention layer only; a convolution layer has none
    none = dict(cfg, layer_types=["conv"] * 5)
    assert lf.forward_flops_per_seq(none, 8192, 0.0)["attention_scores"] == 0.0
    assert lf.flash_train_flops_bytes(none, 2, 8192) == (0.0, 0.0)
    # the flash kernels' least time at the cell's shape, K/V at their 8 heads
    fl, by = lf.flash_train_flops_bytes(cfg, 2, 8192)
    assert fl == 9 * 2.0 * 64 * (8192 * 8193 // 2) * 2 * 32
    assert by == ((2 * 32 + 2 * 8) + (4 * 32 + 2 * 8) + (3 * 32 + 4 * 8)) * 2 * 8192 * 64 * 2
    peaks = pk.peaks_for("TPU v5 lite")
    assert fl / peaks["bf16_flops"] > by / peaks["hbm_bytes_per_s"]       # compute-bound


def test_the_new_readers_read_a_made_up_run_and_nothing_where_nothing_is(monkeypatch):
    from lib import lfm2_flops as lf, peaks as pk, trace as tr
    from readers import lfm2_flash_roofline, lfm2_mfu
    cfg, peaks = _cut(), pk.peaks_for("TPU v5 lite")
    empty = types.SimpleNamespace(cell=None, facts={}, spans=[], trace=None, ops={}, stretch=None,
                                  iterations=0, peaks=None)
    assert lfm2_mfu.read(empty) is None and lfm2_flash_roofline.read(empty) is None
    facts = {"model": cfg, "tokens_per_step": 16384, "rows_per_step": 2, "seq_len": 8192,
             "moe_assignments_held": 4 * 16384.0}
    cell = types.SimpleNamespace(chips=1)
    need = lf.train_flops_per_seq(cfg, 8192, 4 * 8192.0)
    # 3 steps in a second: the share is the need over the peak, never above 100 at a step the
    # peak allows
    ctx = types.SimpleNamespace(cell=cell, facts=facts, stretch=(0.0, 1e9), iterations=3,
                                peaks=peaks, ops={})
    got = lfm2_mfu.read(ctx)
    assert abs(got["value"] - 100.0 * need * 6 / peaks["bf16_flops"]) < 1e-9 and got["value"] < 100
    assert abs(sum(got["forward_share_by_part"].values()) - 1.0) < 1e-9
    # another decoder's model (no convolution keys) is not this reader's
    other = dict(facts, model={k: v for k, v in cfg.items() if k != "conv_L_cache"})
    assert lfm2_mfu.read(types.SimpleNamespace(**{**vars(ctx), "facts": other})) is None
    fl, by = lf.flash_train_flops_bytes(cfg, 2, 8192)
    least = max(fl / peaks["bf16_flops"], by / peaks["hbm_bytes_per_s"])

    def kernel_seconds(events, pattern, lo, hi):
        return (3 * 2 * least, 9) if "dq" in pattern else (0.0, 3)     # fwd once a layer and step
    monkeypatch.setattr(tr, "kernel_seconds", kernel_seconds)
    ctx.ops = {0: [["%flash_fwd.1 = custom-call()", 0, 1, {}]]}
    share = lfm2_flash_roofline.read(ctx)
    assert abs(share["value"] - 50.0) < 1e-6 and share["forward_calls_per_layer"] == 1.0
    assert share["bound"] == "compute"


def test_every_twin_metric_file_points_at_an_accepted_reader_with_its_twins_parameters():
    twins = {"moe_step_ms": "moe.step_ms", "moe_dispatch_ms": "moe.dispatch_ms",
             "moe_expert_load_max": "moe.expert_load_max",
             "grouped_dot_roofline": "mellum2.grouped_dot_roofline", "attn_step_ms": "attn.step_ms",
             "amp_step_ms": "lm.amp_step_ms", "optimizer_ms": "lm.optimizer_ms",
             "adam_roofline": "lm.adam_roofline", "device_idle": "lm.device_idle",
             "unscoped_pct": "lm.unscoped_pct", "import_s": "lm.import_s",
             "model_init_s": "lm.model_init_s", "step_trace_s": "lm.step_trace_s",
             "step_load_s": "lm.step_load_s"}
    load = lambda name: json.load(open(os.path.join(BENCH_DIR, "metrics", name + ".json")))
    for new, old in twins.items():
        mine, theirs = load("lfm2." + new), load(old)
        assert mine["name"] == "lfm2." + new
        assert (mine["reader"], mine.get("params")) == (theirs["reader"], theirs.get("params")), new
    conv, attn = load("lfm2.conv_step_ms"), load("attn.step_ms")
    assert conv["reader"] == attn["reader"] == "module_ms"
    assert {**conv["params"], "modules": None} == {**attn["params"], "modules": None}
    man = json.load(open(os.path.join(bm_util.ROOT, "BENCHMARK.json")))
    mine = [m for m in man["per_layer"] if m["name"].startswith("lfm2.")]
    assert {m["name"] for m in mine} == {"lfm2." + n for n in twins} | {
        "lfm2.conv_step_ms", "lfm2.conv_mix_ms", "lfm2.mfu", "lfm2.flash_roofline"}
    assert all(m["workloads"] == ["lfm2-8b-a1b.pretrain-8k"] for m in mine)
    cells = {w["name"]: w for w in man["workloads"]}
    assert cells["lfm2-8b-a1b.pretrain-8k"]["chips"] == 1
    assert [n for n, w in cells.items() if w["chips"] == 4] == ["bert-large.ddp4-512"]
    assert all(os.path.exists(os.path.join(BENCH_DIR, "metrics", m["name"] + ".json"))
               for m in man["per_layer"])


def test_the_routing_tool_hands_the_reference_a_choice_of_experts():
    """``tools/lfm2_routing.py`` at the tiny configuration: the float32 run's
    own choice given back to it changes nothing, the choice is what the
    reference's router picks with its bias, and another choice moves the
    gradient."""
    import jax
    from lib import weights
    from references import lfm2 as ref
    spec = importlib.util.spec_from_file_location(
        "lfm2_routing", os.path.join(BENCH_DIR, "tools", "lfm2_routing.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    rf = tool._flips_module()
    cfg = _tiny_cfg()
    p = weights.make_weights(tool.shapes(cfg), 5, 0.05)
    ids = jax.numpy.asarray(np.random.RandomState(0).randint(0, cfg["vocab_size"], (1, 32)))
    choice = tool.chosen_experts(ref, p, ids[0], cfg, "float32")
    assert len(choice) == cfg["mlp_layer_types"].count("sparse")
    assert choice[0].shape == (32, cfg["num_experts_per_tok"])
    want = rf.first_gradient(ref, p, ids, cfg, "float32")
    with rf.given(ref, choice):
        same = rf.first_gradient(ref, p, ids, cfg, "float32")
    np.testing.assert_allclose(same, want, rtol=1e-5, atol=1e-7)
    others = [(c + 1) % cfg["num_experts_published"] for c in choice]
    with rf.given(ref, others):
        moved = rf.first_gradient(ref, p, ids, cfg, "float32")
    assert ref.difference_norms(moved, want)[2] > 0.05
    assert ref.difference_norms(same, want)[2] < 1e-5
