"""The ``mellum2`` cell's files on the CPU at the tiny configuration beside
these tests: the cell through ``runners/train_causal_lm`` and
``references/mellum2`` is ``correct``, a step that hands its state back and the
reference one precision down are not, the closed-form FLOPs of the published
cut agree with ISSUE 31's count by hand, and ``grouped_dot_roofline`` reads a
made-up trace (a known time gives a known share, nothing over 100 %) and
returns nothing where the program gave it nothing."""

import json
import os
import types

import numpy as np

import bm_util

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "mellum-tiny.pretrain-lm-32"


def _manifest():
    man = bm_util.manifest()
    man["workloads"].append({"name": CELL, "config": "mellum-tiny", "traffic": "pretrain-lm-32",
                             "chips": 4})
    return man


def _tiny_cfg():
    return json.load(open(os.path.join(bm_util.TINY, "configs", "mellum-tiny.json")))


def _cut():
    return json.load(open(os.path.join(BENCH_DIR, "configs", "mellum2-12b.json")))


def test_mellum_cell_is_correct_on_four_virtual_devices():
    result, lines = bm_util.run(CELL, seed=2**31 + 5, seconds=1.0, man=_manifest())
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 3
    compared = {l["compared"]: l for l in lines if "compared" in l}
    assert {"loss_gap", "grad_diff_mean", "update_norm_gap",
            "moe_dropped_assignments", "moe_held_shortfall", "replicas_differ"} <= set(compared)
    assert compared["moe_dropped_assignments"]["value"] == 0
    # printed beside the worst leaves, not compared
    leaves = next(l["worst_leaves"] for l in lines if "worst_leaves" in l)
    assert 0 < leaves["grad_diff_at_median_leaf"] <= leaves["grad_diff_at_worst_leaf"]
    moe = next(l["moe"] for l in lines if "moe" in l)
    # 4 devices x 1 row x 32 tokens x 4 choices x 4 expert layers, a quarter of them held
    assert 0 < moe["moe_assignments_held"] < 4 * 32 * 4 * 4 and moe["moe_expert_load_max"] > 0


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


def test_controls_fail_where_the_stated_precision_passes():
    """At a size a test can hold, relatively (the limits in references/mellum2.py
    are the chip-size cell's): fp8-rounded matmuls move the first gradient at
    least three times as far as bf16 ones, in what it differs by (the projections)
    as in how long it is, and a bf16 parameter store and a stuck step each break
    the limit that is there for them."""
    import jax
    from apex_tpu import models
    from lib import weights
    from references import mellum2 as ref
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
        assert low["grad_diff_mean"] > 3 * sound["grad_diff_mean"]
    again = ref.compare(ref.train(params, batches, cfg, block_rows=2), want)
    assert max(again[k] for k in limits) < 1e-4          # blocks only reorder the sums
    half = ref.compare(ref.train(params, batches, cfg, param_dtype="bfloat16"), want)
    assert half["update_norm_gap"] > limits["update_norm_gap"] > ref.LIMITS["update_norm_gap"]
    stuck = dict(want, update_norms=np.zeros_like(want["update_norms"]))
    assert ref.compare(stuck, want)["update_norm_gap"] > limits["update_norm_gap"]


def test_reference_imports_nothing_of_the_program():
    src = open(os.path.join(BENCH_DIR, "references", "mellum2.py")).read()
    assert "import apex_tpu" not in src and "from apex_tpu" not in src
    assert "Precision.HIGHEST" in open(os.path.join(BENCH_DIR, "references", "_precision.py")).read()


def test_the_cut_keeps_every_published_width_and_the_readers_keys():
    cfg = _cut()
    assert (cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["sliding_window"], cfg["intermediate_size"]) == (2304, 32, 4, 128, 896, 8, 1024, 7168)
    assert cfg["layer_types"] == ["sliding_attention"] * 3 + ["full_attention"]
    assert cfg["mlp_layer_types"] == ["sparse"] * 4 and cfg["num_hidden_layers"] == 4
    assert cfg["num_experts"] == 16 and cfg["num_experts_published"] == 64
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types", "mlp_layer_types", "num_experts",
                              "vocab_size"]
    assert cfg["vocab_size"] * 4 == cfg["published"]["vocab_size"] == 98304
    # stated as derived, so that the accepted readers read the file unedited
    assert cfg["num_attention_heads_per_layer"] == [32] * 4 and cfg["gating"] is False
    assert cfg["shared_expert_intermediate_size"] == 0 and cfg["moe_routed_scaling_factor"] == 1.0
    assert cfg["rope_parameters"]["full_attention"]["attention_factor"] == 1.2772588722239782
    assert "limits" not in cfg and len(cfg["source"]) < 200


def test_closed_form_flops_of_the_published_cut():
    from lib import laguna_flops as lf
    cfg = _cut()
    parts = lf.forward_flops_per_seq(cfg, 8192, 4 * 8192 * 8 * 16 / 64)
    per_token = {k: v / 8192 / 1e6 for k, v in parts.items()}
    # ISSUE 31's hand count, MFLOP a token over 4 layers: projections 169.9, scores 114.3,
    # routed experts 99.1, router 1.2, the head at a quarter of the vocabulary 113.2
    assert abs(per_token["attention_projections"] - 169.9) < 0.1
    assert abs(per_token["attention_scores"] - 114.3) < 0.5
    assert abs(per_token["routed_experts"] - 99.1) < 0.1 and abs(per_token["router"] - 1.2) < 0.1
    assert abs(per_token["head"] - 113.2) < 0.1
    assert per_token["dense_mlp"] == per_token["shared_expert"] == 0.0
    assert abs(parts["routed_experts"] / sum(parts.values()) - 0.199) < 0.002


def _kernel(name, start, ns, kind="custom-call"):
    return [f"%{name}.1 = {kind}()", start, ns, {"instr": name, "kind": kind}]


def test_grouped_dot_roofline_from_a_made_up_trace(monkeypatch):
    """Two iterations, four layers, the grouped-product kernels a layer that the
    configuration's ``remat`` says (nine; twelve where the forward's three run
    twice) under ``moe.experts`` plus elementwise work there and a kernel of
    another scope: only the scope's kernels count, a known time gives a known
    share, a time at the roofline reads 100 %, not more, and a trace whose count
    of kernels contradicts the configuration reads nothing."""
    from lib import grouped_dot as gd, peaks as pk, phase_table as pt
    from readers import grouped_dot_roofline as reader
    cfg, peaks = _cut(), pk.peaks_for("TPU v5 lite")
    held = 4 * 16384.0                       # a uniform routing's share over four layers
    fl, by = gd.product_flops_bytes(16384.0, 2304, 896, 16)
    assert fl == 2 * 16384 * 2304 * 896 and by == 2 * (16 * 2304 * 896 + 16384 * 3200)
    least = max(fl / peaks["bf16_flops"], by / peaks["hbm_bytes_per_s"])      # s a product
    experts = ("lm.train_step", "model", "layers/0/mlp", "moe.experts")

    def ctx_for(ns_a_kernel, kernels_a_layer=12, kind="custom-call", remat="dots"):
        rows = []
        for it in range(2):
            for k in range(4 * kernels_a_layer):
                rows.append((_kernel("ragged-dot-none", it * 1e9 + k * 1e7, ns_a_kernel, kind),
                             experts, False, pt.TEXT))
            rows.append((_kernel("multiply_fusion", it * 1e9 + 9e8, 5e6, "fusion"), experts,
                         False, pt.TEXT))
            rows.append((_kernel("flash_fwd", it * 1e9 + 9.5e8, 5e6),
                         ("lm.train_step", "model", "layers/0/self_attn"), False, pt.TEXT))
        monkeypatch.setattr(pt, "rows_by_chip", lambda ctx, entry: {0: rows})
        return types.SimpleNamespace(cell=None, facts={"model": dict(cfg, remat=remat),
                                                       "moe_assignments_held": held},
                                     iterations=2, peaks=peaks)

    got = reader.read(ctx_for(4 * least * 1e9), entry="lm.train_step")
    assert got["kernels_per_layer"] == got["products_per_layer"] == 12
    assert abs(got["value"] - 25.0) < 1e-6 and got["bound"] == "compute"
    assert abs(got["ms_per_step"] - 48 * 4 * least * 1e3) < 1e-9
    assert abs(got["scope_ms_per_step"] - got["ms_per_step"] - 5.0) < 1e-9
    assert abs(reader.read(ctx_for(least * 1e9), entry="lm.train_step")["value"] - 100.0) < 1e-6
    # the cell as it is run rematerializes nothing: nine products a layer
    assert cfg["remat"] is None
    nine = reader.read(ctx_for(4 * least * 1e9, kernels_a_layer=9, remat=None), entry="lm.train_step")
    assert nine["kernels_per_layer"] == nine["products_per_layer"] == 9
    assert abs(nine["value"] - 25.0) < 1e-6
    # a kernel that fuses products, one that only prepares tiles, a forward the trace shows
    # twice where the configuration recomputes nothing: the count contradicts the cell
    for kernels_a_layer, remat in ((4, "dots"), (20, "dots"), (12, None), (9, "dots")):
        assert reader.read(ctx_for(least * 1e9, kernels_a_layer, remat=remat),
                           entry="lm.train_step") is None
    # no kernel under the scope (XLA's own fusions): the whole scope's time, a lower reading
    plain = reader.read(ctx_for(least * 1e9, kind="fusion", remat=None), entry="lm.train_step")
    assert plain["kernels_per_layer"] == 0 and plain["products_per_layer"] == 9
    assert plain["value"] < 100.0 * 9 / 12


def test_grouped_dot_roofline_returns_nothing_where_the_program_gave_nothing(monkeypatch):
    from lib import peaks as pk, phase_table as pt
    from readers import grouped_dot_roofline as reader
    empty = types.SimpleNamespace(cell=None, facts={}, spans=[], trace=None, ops={}, stretch=None,
                                  iterations=0, peaks=None)
    assert reader.read(empty, entry="lm.train_step") is None
    # a program without the scope (the parent of a PR that adds it), a model without experts
    other = [(_kernel("flash_fwd", 0.0, 1e6), ("lm.train_step", "model"), False, pt.TEXT)]
    monkeypatch.setattr(pt, "rows_by_chip", lambda ctx, entry: {0: other})
    ctx = types.SimpleNamespace(cell=None, facts={"model": _cut(), "moe_assignments_held": 65536.0},
                                iterations=1, peaks=pk.peaks_for("TPU v5 lite"))
    assert reader.read(ctx, entry="lm.train_step") is None
    ctx.facts = {"model": {"hidden_size": 1024}, "moe_assignments_held": None}
    assert reader.read(ctx, entry="lm.train_step") is None


def test_every_new_metric_file_points_at_an_accepted_reader_with_its_twins_parameters():
    twins = {"moe_step_ms": "moe.step_ms", "moe_dispatch_ms": "moe.dispatch_ms",
             "moe_expert_load_max": "moe.expert_load_max", "attn_step_ms": "attn.step_ms",
             "mfu": "lm.mfu", "flash_band_roofline": "kernel.flash_band_roofline",
             "amp_step_ms": "lm.amp_step_ms", "optimizer_ms": "lm.optimizer_ms",
             "adam_roofline": "lm.adam_roofline", "device_idle": "lm.device_idle",
             "unscoped_pct": "lm.unscoped_pct", "import_s": "lm.import_s",
             "model_init_s": "lm.model_init_s", "step_trace_s": "lm.step_trace_s",
             "step_load_s": "lm.step_load_s"}
    load = lambda name: json.load(open(os.path.join(BENCH_DIR, "metrics", name + ".json")))
    for new, old in twins.items():
        mine, theirs = load("mellum2." + new), load(old)
        assert mine["name"] == "mellum2." + new
        assert (mine["reader"], mine.get("params")) == (theirs["reader"], theirs.get("params")), new
    man = json.load(open(os.path.join(bm_util.ROOT, "BENCHMARK.json")))
    mine = [m for m in man["per_layer"] if m["name"].startswith("mellum2.")]
    assert {m["name"] for m in mine} == {"mellum2." + n for n in twins} | {"mellum2.grouped_dot_roofline"}
    assert all(m["workloads"] == ["mellum2-12b.pretrain-8k"] for m in mine)


def test_routing_flips_hands_the_reference_a_choice_of_experts():
    """``tools/routing_flips.py`` at the tiny configuration: the float32 run's
    own choice given back to it changes nothing, the choice is what the
    reference's router picks, and another choice moves the gradient."""
    import importlib.util
    import jax
    from lib import weights
    from references import mellum2 as ref
    spec = importlib.util.spec_from_file_location(
        "routing_flips", os.path.join(BENCH_DIR, "tools", "routing_flips.py"))
    rf = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(rf)
    cfg = _tiny_cfg()
    p = weights.make_weights(rf.shapes(cfg), 5, 0.05)
    ids = jax.numpy.asarray(np.random.RandomState(0).randint(0, cfg["vocab_size"], (1, 32)))
    choice = rf.chosen_experts(ref, p, ids[0], cfg, "float32")
    assert len(choice) == cfg["num_hidden_layers"]
    assert choice[0].shape == (32, cfg["num_experts_per_tok"])
    want = rf.first_gradient(ref, p, ids, cfg, "float32")
    with rf.given(ref, choice):
        same = rf.first_gradient(ref, p, ids, cfg, "float32")
    np.testing.assert_allclose(same, want, rtol=1e-5, atol=1e-7)
    others = [(c + 1) % cfg["num_experts_published"] for c in choice]
    with rf.given(ref, others):
        moved = rf.first_gradient(ref, p, ids, cfg, "float32")
    names = [jax.tree_util.keystr(path)
             for path, _ in jax.tree_util.tree_flatten_with_path(rf.shapes(cfg))[0]]
    assert rf.reading(ref, moved, want, names)["grad_diff_mean"] > 0.05
    assert rf.reading(ref, same, want, names)["grad_diff_mean"] < 1e-5
