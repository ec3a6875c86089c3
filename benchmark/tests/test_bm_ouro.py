"""The ``ouro`` cell's files on the CPU at the tiny configuration beside these
tests: the cell through ``runners/train_looped_lm`` and ``references/ouro`` is
``correct`` and reports its two facts, the reference one precision down and a
step that leaves the gate's bias out are not, the cut keeps every published
width and ``total_ut_steps``, the closed-form FLOPs agree with ISSUE 40's count
by hand and stay under the peak, every twin metric file equals its twin's
parameters but for ``grad_bytes``, and the third control's tool reads what a
narrower sum over the passes changes."""

import importlib.util
import json
import os
import types

import numpy as np

import bm_util

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "ouro-tiny.pretrain-lm-32"
NEW = "ouro-2.6b.pretrain-8k"
# the catalog row's ``config``, but for the two keys the cut changes
PUBLISHED = {"head_dim": 128, "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5632,
             "max_position_embeddings": 65536, "max_window_layers": 48, "model_type": "ouro",
             "num_attention_heads": 16, "num_key_value_heads": 16, "rms_norm_eps": 1e-06,
             "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
             "tie_word_embeddings": False, "total_ut_steps": 4, "early_exit_threshold": 1,
             "use_sliding_window": False, "vocab_size": 49152}


def _manifest():
    man = bm_util.manifest()
    man["workloads"].append({"name": CELL, "config": "ouro-tiny", "traffic": "pretrain-lm-32",
                             "chips": 4})
    return man


def _tiny_cfg():
    return json.load(open(os.path.join(bm_util.TINY, "configs", "ouro-tiny.json")))


def _cut():
    return json.load(open(os.path.join(BENCH_DIR, "configs", "ouro-2.6b.json")))


def test_ouro_cell_is_correct_on_four_virtual_devices_and_reports_its_exit_facts():
    result, lines = bm_util.run(CELL, seed=2**31 + 5, seconds=1.0, man=_manifest())
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 3
    compared = {l["compared"]: l for l in lines if "compared" in l}
    assert {"loss_gap", "grad_diff_mean", "gate_grad_gap", "update_norm_gap",
            "replicas_differ"} <= set(compared)
    # no expert layer: the accepted runner's counters are not compared here
    assert "moe_dropped_assignments" not in compared and "moe_held_shortfall" not in compared
    exit_line = next(l for l in lines if "exit" in l)
    assert 1.0 < exit_line["exit"]["exit_mean_step"] < 4.0
    assert 0.0 < exit_line["first_step"]["nll_last"] < 2 * np.log(64)
    assert set(exit_line["last_step"]) == {"exit_mean_step", "nll_last"}


def test_controls_and_a_model_without_its_gate_bias_fail_where_the_stated_precision_passes():
    """At a size a test can hold, relatively (the limits in references/ouro.py
    are the chip-size cell's): fp8-rounded matmuls move the first gradient at
    least twice as far as bf16 ones, a bf16 parameter store breaks the limit
    that is there for it, and the same weights with the gate's bias left out
    are another model by the gate's own gradient (the seed draws the bias so)."""
    import jax
    from apex_tpu import models
    from lib import weights
    from references import ouro as ref
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
        assert low["grad_diff_mean"] > 2 * sound["grad_diff_mean"]
    again = ref.compare(ref.train(params, batches, cfg, block_rows=2), want)
    assert max(again[k] for k in limits) < 1e-4          # blocks only reorder the sums
    half = ref.compare(ref.train(params, batches, cfg, param_dtype="bfloat16"), want)
    assert half["update_norm_gap"] > limits["update_norm_gap"] > ref.LIMITS["update_norm_gap"]
    bias = params["exit_gate"]["bias"]
    assert float(abs(bias).max()) > 0 and want["gate_leaves"] == [1, 2]     # drawn from the seed
    # a program that leaves the bias out gives it no gradient and never moves it
    got = {k: np.array(v) if k != "losses" else v for k, v in want.items() if k != "gate_leaves"}
    got["first_grad_norms"][1] = 0.0
    got["update_norms"][1] = 0.0
    without = ref.compare(got, want)
    # 1.0, or the bias's share of a hundredth of the gate's whole gradient where it is under that
    assert 0.3 < without["gate_grad_gap"] <= 1.0 + 1e-6 and 0.3 > ref.LIMITS["gate_grad_gap"]
    # and nothing else sees it: a mean over the leaves does not, and the update's worst leaf is
    # read against the median leaf's norm, under which a leaf of one number disappears
    assert without["grad_diff_mean"] < limits["grad_diff_mean"]
    assert without["update_norm_gap"] < limits["update_norm_gap"]


def test_reference_imports_nothing_of_the_program_and_loops_in_plain_python():
    src = open(os.path.join(BENCH_DIR, "references", "ouro.py")).read()
    assert "import apex_tpu" not in src and "from apex_tpu" not in src
    assert "lax.scan" not in src and 'for t in range(cfg["total_ut_steps"])' in src
    assert "Precision.HIGHEST" in open(os.path.join(BENCH_DIR, "references", "_precision.py")).read()


def test_the_cut_keeps_every_published_width_and_the_readers_keys():
    cfg = _cut()
    for key, value in PUBLISHED.items():
        assert cfg[key] == value, key
    assert cfg["head_dim"] * cfg["num_attention_heads"] == cfg["hidden_size"]
    assert cfg["num_hidden_layers"] == 6 and cfg["layer_types"] == ["full_attention"] * 6
    assert cfg["mlp_layer_types"] == ["dense"] * 6
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types"]
    assert cfg["published"]["num_hidden_layers"] == 48 == 8 * cfg["num_hidden_layers"]
    man = json.load(open(os.path.join(bm_util.ROOT, "BENCHMARK.json")))
    entry = next(c for c in man["configs"] if c["name"] == "ouro-2.6b")
    assert entry["reduced"] == cfg["reduced"] and len(entry["source"]) < 200
    assert entry["source"] in cfg["source"]
    # no expert key anywhere: the decoder builds without them
    assert not any(k in cfg for k in ("num_experts", "num_experts_per_tok", "moe_intermediate_size"))
    # stated as derived or assumed, so that the runner and readers read the file unedited
    assert cfg["shared_expert_intermediate_size"] == 0 and cfg["gating"] is False
    assert cfg["qk_norm"] is False and cfg["sandwich_norm"] is True and cfg["exit_beta"] == 0.05
    assert cfg["rope_parameters"] == {"full_attention": {"rope_type": "default",
                                                         "rope_theta": 1000000}}
    assumed = cfg["assumed"]
    assert {"loop_norm", "sandwich_norm", "exit_gate", "exit_loss", "exit_beta", "loop_grad_dtype",
            "init_std", "remat", "planned_bytes", "head_chunk", "optimizer"} <= set(assumed)
    assert set(assumed["planned_bytes"]) >= {"remat_nothing", "remat_dots", "remat_none"}
    assert cfg["remat"] == "nothing" and cfg["per_chip_batch"] == 1 and "limits" not in cfg
    assert cfg["runner"] == "train_looped_lm" and cfg["reference"] == "ouro"
    # 6 layers, the untied embedding and head, the final norm, the gate
    layer = 4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048
    assert 6 * layer + 2 * 49152 * 2048 + 2048 + 2049 == 509_661_185


def test_closed_form_flops_of_the_published_cut():
    from lib import ouro_flops as of, peaks as pk
    cfg = _cut()
    parts = of.forward_flops_per_seq(cfg, 8192)
    per_token = {k: v / 8192 / 1e6 for k, v in parts.items()}
    # ISSUE 40's hand count, MFLOP a token: a block application is 33.6 in the four
    # projections, 33.6 in the scores at 8192 tokens (16 heads of 128, the causal half) and
    # 69.2 in the SwiGLU, 136.3; 6 blocks x 4 passes are 3271, the head at 201.3 a pass 805
    assert abs(per_token["attention_projections"] / 24 - 33.55) < 0.01
    assert abs(per_token["attention_scores"] / 24 - 33.56) < 0.01
    assert abs(per_token["dense_mlp"] / 24 - 69.21) < 0.01
    blocks = sum(per_token[k] for k in ("attention_projections", "attention_scores", "dense_mlp"))
    assert abs(blocks - 3271) < 1.5 and abs(per_token["head"] - 805.2) < 0.2
    assert per_token["exit_gate"] == 3 * 2 * 2048 / 1e6
    assert abs(per_token["head"] / sum(per_token.values()) - 0.1975) < 0.001
    step = of.train_flops_per_seq(cfg, 8192)
    assert abs(step / 1e12 - 100.2) < 0.1                # 100.2 TFLOP a sequence of 8192
    # one pass of the same layers is a quarter of the blocks and of the head
    once = of.forward_flops_per_seq(dict(cfg, total_ut_steps=1), 8192)
    assert once["dense_mlp"] * 4 == parts["dense_mlp"] and once["head"] * 4 == parts["head"]
    assert once["exit_gate"] == 0.0
    # the flash kernels' least time at the cell's shape, K/V at their 16 heads, 24 applications
    fl, by = of.flash_train_flops_bytes(cfg, 1, 8192, forward_calls=2.0)
    assert fl == 24 * 11 * 2.0 * 128 * (8192 * 8193 // 2) * 16
    assert by == 24 * (2 * 64 + (4 * 16 + 2 * 16) + (3 * 16 + 4 * 16)) * 8192 * 128 * 2
    peaks = pk.peaks_for("TPU v5 lite")
    assert fl / peaks["bf16_flops"] > by / peaks["hbm_bytes_per_s"]       # compute-bound


def test_the_new_readers_read_a_made_up_run_and_nothing_where_nothing_is(monkeypatch):
    from lib import ouro_flops as of, peaks as pk, trace as tr
    from readers import fact, ouro_flash_roofline, ouro_mfu
    cfg, peaks = _cut(), pk.peaks_for("TPU v5 lite")
    empty = types.SimpleNamespace(cell=None, facts={}, spans=[], trace=None, ops={}, stretch=None,
                                  iterations=0, peaks=None)
    assert ouro_mfu.read(empty) is None and ouro_flash_roofline.read(empty) is None
    assert fact.read(empty, key="exit_mean_step") is None
    facts = {"model": cfg, "tokens_per_step": 8192, "rows_per_step": 1, "seq_len": 8192,
             "exit_mean_step": 1.9}
    cell = types.SimpleNamespace(chips=1)
    need = of.train_flops_per_seq(cfg, 8192)
    # 3 steps in 4.5 seconds: the share is the need over the peak, under 100 at a step the
    # peak allows
    ctx = types.SimpleNamespace(cell=cell, facts=facts, stretch=(0.0, 4.5e9), iterations=3,
                                peaks=peaks, ops={})
    got = ouro_mfu.read(ctx)
    assert abs(got["value"] - 100.0 * need / 1.5 / peaks["bf16_flops"]) < 1e-9 and got["value"] < 100
    assert abs(sum(got["forward_share_by_part"].values()) - 1.0) < 1e-9
    assert fact.read(ctx, key="exit_mean_step") == 1.9
    # another decoder's model (no loop) is not this reader's
    other = dict(facts, model={k: v for k, v in cfg.items() if k != "total_ut_steps"})
    assert ouro_mfu.read(types.SimpleNamespace(**{**vars(ctx), "facts": other})) is None
    fl, by = of.flash_train_flops_bytes(cfg, 1, 8192, forward_calls=2.0)
    least = max(fl / peaks["bf16_flops"], by / peaks["hbm_bytes_per_s"])

    def kernel_seconds(events, pattern, lo, hi):
        # a forward run twice an application and step (the block is rematerialized)
        return (3 * 2 * least, 3 * 96) if "dq" in pattern else (0.0, 3 * 48)
    monkeypatch.setattr(tr, "kernel_seconds", kernel_seconds)
    ctx.ops = {0: [["%flash_fwd.1 = custom-call()", 0, 1, {}]]}
    share = ouro_flash_roofline.read(ctx)
    assert abs(share["value"] - 50.0) < 1e-6 and share["forward_calls_per_application"] == 2.0
    assert share["bound"] == "compute"
    ctx.facts = other
    assert ouro_flash_roofline.read(ctx) is None


def test_every_twin_metric_file_points_at_an_accepted_reader_with_its_twins_parameters():
    twins = {"attn_step_ms": "attn.step_ms", "amp_step_ms": "lm.amp_step_ms",
             "optimizer_ms": "lm.optimizer_ms", "device_idle": "lm.device_idle",
             "unscoped_pct": "lm.unscoped_pct", "import_s": "lm.import_s",
             "model_init_s": "lm.model_init_s", "step_trace_s": "lm.step_trace_s",
             "step_load_s": "lm.step_load_s"}
    load = lambda name: json.load(open(os.path.join(BENCH_DIR, "metrics", name + ".json")))
    for new, old in twins.items():
        mine, theirs = load("ouro." + new), load(old)
        assert mine["name"] == "ouro." + new
        assert (mine["reader"], mine.get("params")) == (theirs["reader"], theirs.get("params")), new
    # Adam's share through the accepted reader, with the gradient at the 2 bytes the kernel
    # reads of a bf16 leaf since PR 39: the one parameter in which a twin differs
    adam, theirs = load("ouro.adam_roofline"), load("lm.adam_roofline")
    assert adam["reader"] == theirs["reader"] == "adam_roofline"
    assert adam["params"] == {**theirs.get("params", {}), "grad_bytes": 2}
    attn = load("attn.step_ms")
    for name in ("ouro.mlp_step_ms", "ouro.norm_ms"):
        mine = load(name)
        assert mine["reader"] == attn["reader"] == "module_ms"
        assert {**mine["params"], "modules": None} == {**attn["params"], "modules": None}
    for name, scope in (("ouro.loop_ms", "loop"), ("ouro.head_ms", "loss")):
        assert load(name) == {"name": name, "reader": "phase_ms",
                              "params": {"entry": "lm.train_step", "within": [scope]}}
    assert load("ouro.exit_mean_step")["reader"] == "fact"
    man = json.load(open(os.path.join(bm_util.ROOT, "BENCHMARK.json")))
    mine = [m for m in man["per_layer"] if m["name"].startswith("ouro.")]
    assert {m["name"] for m in mine} == {"ouro." + n for n in twins} | {
        "ouro.adam_roofline", "ouro.loop_ms", "ouro.head_ms", "ouro.mlp_step_ms", "ouro.norm_ms",
        "ouro.exit_mean_step", "ouro.mfu", "ouro.flash_roofline"}
    assert all(m["workloads"] == [NEW] for m in mine)
    listed = {m["name"] for m in man["end_to_end"] + man["per_layer"]
              if NEW in m.get("workloads", [])} - {m["name"] for m in mine}
    assert listed == {"train.samples_per_s", "step.inferred_phase_pct", "step.mixed_fusion_pct",
                      "lm.pack_ms"}
    assert len(man["workloads"]) == 6 and sum(w["chips"] == 4 for w in man["workloads"]) == 1
    assert all(os.path.exists(os.path.join(BENCH_DIR, "metrics", m["name"] + ".json"))
               for m in man["per_layer"])


def test_the_norm_metric_reads_the_four_norms_and_the_loops_and_nothing_else():
    import re
    want = re.compile(json.load(open(os.path.join(
        BENCH_DIR, "metrics", "ouro.norm_ms.json")))["params"]["modules"])
    for module in ("layers/0/input_layernorm", "layers/3/input_layernorm_2",
                   "layers/5/post_attention_layernorm", "layers/2/post_attention_layernorm_2",
                   "norm"):
        assert want.search(module), module
    for module in ("layers/0/self_attn/q_proj", "layers/1/mlp/up_proj", "embed_tokens", "loop",
                   "loop.norm"):
        assert not want.search(module), module


def test_the_third_controls_tool_reads_what_a_narrower_sum_over_the_passes_changes():
    """``tools/control_loop_grad.py`` at the tiny configuration: a float32
    carry gives the reference's own gradient back; a bfloat16 carry moves the
    stack's weights a little and nothing else."""
    import jax
    import jax.numpy as jnp
    from lib import weights
    from references import ouro as ref
    spec = importlib.util.spec_from_file_location(
        "control_loop_grad", os.path.join(BENCH_DIR, "tools", "control_loop_grad.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    cfg = _tiny_cfg()
    p = weights.make_weights(tool.shapes(cfg), 5, 0.05)
    ids = jnp.asarray(np.random.RandomState(0).randint(0, cfg["vocab_size"], (1, 32)))
    want = np.asarray(tool.first_gradients(ref, p, ids, cfg, "float32"))
    same = np.asarray(tool.first_gradients(ref, p, ids, cfg, "float32", jnp.float32))
    np.testing.assert_allclose(same, want, rtol=2e-5, atol=1e-7)
    narrow = np.asarray(tool.first_gradients(ref, p, ids, cfg, "float32", jnp.bfloat16))
    rel = ref.leaf_differences(narrow, want)
    names = [jax.tree_util.keystr(path) for path, _ in jax.tree_util.tree_leaves_with_path(p)]
    stack = np.array(["layers" in n for n in names])
    assert 1e-4 < rel[stack].max() < 2e-2 and rel[~stack].max() < 1e-6
