"""The ``kanana2`` cell's files on the CPU at the tiny configuration beside these
tests: the cell through ``runners/train_causal_lm`` and ``references/kanana2`` is
``correct``; a program whose shared key head is not rotated, or whose key head's
gradient is one query head's and not the sum, is not; the reference one precision
down is not either; the reference is float32, ``highest``, free of the program
and materializes K and V head by head; the cut keeps every published width; the
closed-form FLOPs agree with ISSUE 48's count by hand at the published widths; the
new readers read a made-up run and nothing where nothing is; every twin metric
file equals its twin's parameters."""

import json
import os
import types

import numpy as np
import pytest

import bm_util

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "kanana2-tiny.pretrain-lm-32"
NEW = "kanana-2-30b-a3b.pretrain-8k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
# the catalog row's ``config``, but for the three keys the cut changes
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144, "kv_lora_rank": 512,
    "max_position_embeddings": 32768, "model_type": "deepseek_v3", "moe_intermediate_size": 768,
    "moe_layer_freq": 1, "n_group": 1, "n_shared_experts": 2, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 6, "num_key_value_heads": 32,
    "q_lora_rank": None, "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_interleave": True, "rope_scaling": None, "rope_theta": 1000000,
    "routed_scaling_factor": 2.448, "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128}


def _manifest():
    man = bm_util.manifest()
    man["workloads"].append({"name": CELL, "config": "kanana2-tiny",
                             "traffic": "pretrain-lm-32", "chips": 4})
    return man


def _tiny_cfg():
    return json.load(open(os.path.join(bm_util.TINY, "configs", "kanana2-tiny.json")))


def _cut():
    return json.load(open(os.path.join(BENCH_DIR, "configs", "kanana-2-30b-a3b.json")))


def _read(lines, name):
    return next(l["value"] for l in lines if l.get("compared") == name)


def test_kanana2_cell_is_correct_on_four_virtual_devices():
    result, lines = bm_util.run(CELL, seed=2**31 + 5, seconds=1.0, man=_manifest())
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 3
    compared = {l["compared"]: l for l in lines if "compared" in l}
    assert {"loss_gap", "grad_diff_mean", "grad_norm_own_worst", "grad_diff_own_5th", "update_norm_gap",
            "moe_dropped_assignments", "moe_held_shortfall", "replicas_differ"} <= set(compared)
    assert compared["moe_dropped_assignments"]["value"] == 0
    moe = next(l["moe"] for l in lines if "moe" in l)
    # 4 devices x 1 row x 32 tokens x 4 choices x 4 expert layers, a quarter of them held
    assert 0 < moe["moe_assignments_held"] < 4 * 32 * 4 * 4 and moe["moe_expert_load_max"] > 0


def _planted(fault):
    """``tools/kanana2_faults.py``'s ``planted``: the one home of the two faults."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "kanana2_faults", os.path.join(BENCH_DIR, "tools", "kanana2_faults.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool.planted(fault)


@pytest.mark.parametrize("fault", ["unrotated", "one_head"])
def test_a_planted_fault_of_the_mechanism_is_not_correct(fault):
    """The runner at the tiny size: sound it reads ``correct`` true; with the one
    key head left unrotated, or its gradient taken from query head 0 and not the
    sum over the four, false, by the fifth-worst leaf's difference over its own
    norm (the key head's own projection, one a layer, whose whole gradient is that
    sum; read 0.15 sound, 0.81 and 0.71 under the faults), and under the second
    fault by the worst leaf's length too (0.10 sound, 0.52)."""
    sound, lines = bm_util.run(CELL, seed=11, seconds=0.3, man=_manifest())
    assert sound["correct"] is True
    with _planted(fault):
        broken, broken_lines = bm_util.run(CELL, seed=11, seconds=0.3, man=_manifest())
    assert broken["correct"] is False
    limits = _tiny_cfg()["limits"]
    assert _read(broken_lines, "grad_diff_own_5th") > 1.3 * limits["grad_diff_own_5th"]
    assert limits["grad_diff_own_5th"] > 2 * _read(lines, "grad_diff_own_5th")
    short = _read(broken_lines, "grad_norm_own_worst") > limits["grad_norm_own_worst"]
    assert short == (fault == "one_head")
    assert limits["grad_norm_own_worst"] > 2 * _read(lines, "grad_norm_own_worst")


def test_controls_fail_where_the_stated_precision_passes():
    """At a size a test can hold, relatively (the limits in references/kanana2.py
    are the chip-size cell's): fp8-rounded matmuls move the first gradient at
    least twice as far as bf16 ones, and a bf16 parameter store breaks the limit
    that is there for it."""
    import jax
    from apex_tpu import models
    from lib import weights
    from references import kanana2 as ref
    from runners.train_causal_lm import causal_lm_batch
    cfg = _tiny_cfg()
    limits = cfg["limits"]
    model = models.DeepseekV3(models.DeepseekV3Config.from_dict(cfg))
    shapes = jax.eval_shape(lambda k: model.init(k)[0], jax.random.PRNGKey(0))
    params = weights.make_weights(shapes, seed=5, std=cfg["init_std"])
    batches = [causal_lm_batch({"seq_len": 32}, 5, i, 4, 64) for i in range(2)]
    train = lambda **kw: ref.train(params, batches, cfg, keep=True, **kw)
    want = train()
    sound = ref.compare(train(precision="bfloat16"), want)
    low = ref.compare(train(precision="fp8"), want)
    assert all(sound[k] < limits[k] for k in limits), sound
    assert low["grad_diff_mean"] > 2 * sound["grad_diff_mean"]
    again = ref.compare(train(block_rows=2), want)
    assert max(again[k] for k in limits) < 1e-4          # blocks only reorder the sums
    half = ref.compare(train(param_dtype="bfloat16"), want)
    assert half["update_norm_gap"] > limits["update_norm_gap"] > ref.LIMITS["update_norm_gap"]
    assert set(ref.LIMITS) <= set(limits) | {"grad_diff_mean"}


def test_reference_is_float32_highest_free_of_the_program_and_builds_k_and_v_head_by_head():
    src = open(os.path.join(BENCH_DIR, "references", "kanana2.py")).read()
    assert "import apex_tpu" not in src and "from apex_tpu" not in src
    assert 'reshape(T, H, dn)' in src and 'reshape(T, H, dv)' in src and "jax.checkpoint" in src
    # the one rotated key head against every head's rope part, K's two parts never joined
    assert 'P.einsum("qhd,sd->hqs", cut(q_r), k_r, precision)' in src
    assert "Precision.HIGHEST" in open(os.path.join(BENCH_DIR, "references",
                                                    "_precision.py")).read()


def test_the_cut_keeps_every_published_width_and_the_readers_keys():
    cfg = _cut()
    for key, value in PUBLISHED.items():
        assert cfg[key] == value, key
    if os.path.exists(CATALOG):             # the catalog beside the guide, where it is installed
        row = next(json.loads(l) for l in open(CATALOG)
                   if '"name": "kanana-2-30b-a3b-instruct-2601"' in l)
        assert {k: v for k, v in row["config"].items() if k not in REDUCED} == PUBLISHED
        assert row["source_url"] in cfg["source"]
        assert {k: row["config"][k] for k in REDUCED} == {k: cfg["published"][k] for k in REDUCED}
    assert cfg["reduced"] == REDUCED
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"], cfg["vocab_size"]) == (5, 16, 16032)
    assert cfg["num_experts"] == 16 and cfg["experts_held_start"] == 0
    assert cfg["num_experts_published"] == cfg["published"]["n_routed_experts"] == 128
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"] == 128256
    assert cfg["published"]["num_hidden_layers"] == 48 and "8" in cfg["deployment"]
    man = json.load(open(os.path.join(bm_util.ROOT, "BENCHMARK.json")))
    entry = next(c for c in man["configs"] if c["name"] == "kanana-2-30b-a3b")
    assert entry["reduced"] == cfg["reduced"] and entry["source"] in cfg["source"]
    # what the accepted runner and readers look for, stated as derived
    assert cfg["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert cfg["layer_types"] == ["full_attention"] * 5
    assert set(cfg["derived"]) >= {"layer_types", "mlp_layer_types", "num_experts"}
    assert cfg["runner"] in ("train_causal_lm", "train_balanced_lm") and cfg["reference"] == "kanana2"
    # one learning rate in the example's argv, in the reference's Adam and in the tiny file
    from references import kanana2 as ref
    lr = cfg["argv"][cfg["argv"].index("--lr") + 1]
    assert float(lr) == ref.ADAM["lr"] == 1e-4 and _tiny_cfg()["argv"].count(lr) == 1
    assert cfg["per_chip_batch"] == 2 and cfg["moe_row_buffer_factor"] == 2.0
    assert cfg["head_chunk"] * 4 == cfg["vocab_size"] and "limits" not in cfg
    assert cfg["window_steps"] % 8 == 0
    assumed = cfg["assumed"]
    assert {"router", "router_aux_loss", "expert_bias", "init_std", "remat", "planned_bytes",
            "window_steps", "optimizer", "per_chip_batch", "moe_row_buffer_factor", "head_chunk",
            "training_context", "weight_layout"} <= set(assumed)
    assert set(assumed["planned_bytes"]) >= {"remat_nothing", "remat_dots", "remat_none"}
    # the parameters at the floors' own shape: 575.96 M
    d = 2048
    attn = d * 32 * 192 + d * 576 + 512 * 32 * 256 + 32 * 128 * d + 512
    dense, moe = 3 * d * 6144, 16 * 3 * d * 768 + 3 * d * 1536 + d * 128 + 128
    assert 5 * (attn + 2 * d) + dense + 4 * moe + 2 * 16032 * d + d == 575_955_968


def test_closed_form_flops_of_the_published_cut():
    from lib import kanana2_flops as kf, laguna_flops, peaks as pk
    cfg = _cut()
    held = 4 * 8192 * 6 * 16 / 128               # a uniform routing's share, 4 expert layers
    parts = kf.forward_flops_per_seq(cfg, 8192, held)
    per_token = {k: v / 8192 / 1e6 for k, v in parts.items()}
    # ISSUE 48's hand count, MFLOP a token: a layer's four projections 52.7 (12.583 + 1.180 +
    # 4.194 + 8.389 M weights), its causal scores and values 83.9 at 8192; the dense SwiGLU
    # 75.5; the shared experts 18.9 a layer, a router 0.5, the routed experts 28 at 0.75 held
    # assignments a token; the head 65.7
    assert kf.attention_weights(cfg) == 12_582_912 + 1_179_648 + 4_194_304 + 8_388_608
    assert abs(per_token["attention_projections"] / 5 - 52.69) < 0.01
    assert abs(per_token["attention_scores"] / 5 - 83.90) < 0.02
    assert abs(per_token["dense_mlp"] - 75.50) < 0.01
    assert abs(per_token["shared_experts"] / 4 - 18.87) < 0.01
    assert abs(per_token["router"] / 4 - 0.524) < 0.001
    assert abs(per_token["routed_experts"] - 28.31) < 0.01
    assert abs(per_token["head"] - 65.66) < 0.02
    assert abs(sum(per_token.values()) - 929.0) < 1.5
    assert abs(kf.train_flops_per_seq(cfg, 8192, held) / 1e12 - 22.83) < 0.05
    share = (per_token["attention_projections"] + per_token["attention_scores"]) / sum(
        per_token.values())
    assert 0.72 < share < 0.75                  # latent attention is the cell
    # the kernels at 192 / 128: 320, 512 and 640 numbers a pair and head, twice for a
    # multiply-add; never a padded 256
    pairs = laguna_flops.visible_pairs(8192)
    fl, by = kf.flash_train_flops_bytes(cfg, 2, 8192)
    assert fl == 5 * 2.0 * (320 + 512 + 640) * pairs * 2 * 32
    assert by == 5 * (640 + 960 + 1088) * 32 * 2 * 8192 * 2
    again, _ = kf.flash_train_flops_bytes(cfg, 2, 8192, forward_calls=2.0)
    assert abs((again - fl) / (5 * 2.0 * 320 * pairs * 2 * 32) - 1.0) < 1e-12
    peaks = pk.peaks_for("TPU v5 lite")
    assert fl / peaks["bf16_flops"] > 5 * by / peaks["hbm_bytes_per_s"]     # compute-bound


def test_the_new_readers_read_a_made_up_run_and_nothing_where_nothing_is():
    from lib import kanana2_flops as kf, peaks as pk
    from readers import kanana2_flash_roofline, kanana2_mfu
    cfg, peaks = _cut(), pk.peaks_for("TPU v5 lite")
    empty = types.SimpleNamespace(cell=None, facts={}, spans=[], trace=None, ops={}, stretch=None,
                                  iterations=0, peaks=None)
    assert kanana2_mfu.read(empty) is None and kanana2_flash_roofline.read(empty) is None
    held = 4 * 12288.0
    facts = {"model": cfg, "tokens_per_step": 16384, "rows_per_step": 2, "seq_len": 8192,
             "moe_assignments_held": held}
    cell = types.SimpleNamespace(chips=1)
    need = kf.train_flops_per_seq(cfg, 8192, held / 2)
    fl, by = kf.flash_train_flops_bytes(cfg, 2, 8192)
    least_ns = fl / peaks["bf16_flops"] * 1e9
    # three iterations of 0.5 s; a layer's three kernels, each at half its compute roofline
    ev = lambda name, start, ns: [f"%{name} = bf16[] custom-call()", start, ns,
                                  {"instr": name.split(".")[0], "kind": "custom-call"}]
    events = []
    for it in range(3):
        for layer in range(5):
            for k, (kernel, part) in enumerate((("flash_fwd", 320), ("flash_dq", 512),
                                                ("flash_dkv", 640))):
                events.append(ev(f"{kernel}.{layer}", it * 0.5e9 + (3 * layer + k) * 2e7,
                                 2 * least_ns / 5 * part / 1472))
    ctx = types.SimpleNamespace(cell=cell, facts=facts, stretch=(0.0, 1.5e9), iterations=3,
                                peaks=peaks, ops={0: events})
    got = kanana2_mfu.read(ctx)
    assert abs(got["value"] - 100.0 * 2 * need / 0.5 / peaks["bf16_flops"]) < 1e-9
    assert got["value"] < 100 and abs(sum(got["forward_share_by_part"].values()) - 1.0) < 1e-9
    share = kanana2_flash_roofline.read(ctx)
    assert abs(share["value"] - 50.0) < 1e-6 and share["bound"] == "compute"
    assert share["forward_calls_per_layer"] == 1.0
    # another family's configuration, or a trace without the kernels: nothing
    other = dict(facts, model={k: v for k, v in cfg.items() if k != "kv_lora_rank"})
    for reader in (kanana2_mfu, kanana2_flash_roofline):
        assert reader.read(types.SimpleNamespace(**{**vars(ctx), "facts": other})) is None
    assert kanana2_flash_roofline.read(types.SimpleNamespace(**{**vars(ctx), "ops": {0: []}})) is None


def test_every_twin_metric_file_points_at_an_accepted_reader_with_its_twins_parameters():
    twins = {"moe_step_ms": "lfm2.moe_step_ms", "moe_dispatch_ms": "lfm2.moe_dispatch_ms",
             "moe_expert_load_max": "lfm2.moe_expert_load_max", "mla_step_ms": "lfm2.attn_step_ms",
             "grouped_dot_roofline": "lfm2.grouped_dot_roofline", "amp_step_ms": "lm.amp_step_ms",
             "optimizer_ms": "lm.optimizer_ms", "device_idle": "lm.device_idle",
             "unscoped_pct": "lm.unscoped_pct", "import_s": "lm.import_s",
             "model_init_s": "lm.model_init_s", "step_trace_s": "lm.step_trace_s",
             "step_load_s": "lm.step_load_s", "adam_roofline": "nemotron3.adam_roofline"}
    load = lambda name: json.load(open(os.path.join(BENCH_DIR, "metrics", name + ".json")))
    for new, old in twins.items():
        mine, theirs = load("kanana2." + new), load(old)
        assert mine["name"] == "kanana2." + new
        assert (mine["reader"], mine.get("params")) == (theirs["reader"], theirs.get("params")), new
    scopes = lambda name: (load(name)["reader"], load(name)["params"]["within"])
    assert scopes("kanana2.mla_proj_ms") == ("phase_ms", ["mla.q_proj", "mla.kv_down",
                                                          "mla.kv_norm", "mla.kv_up", "mla.o_proj"])
    assert scopes("kanana2.mla_rope_ms") == ("phase_ms", ["mla.rope"])
    assert load("kanana2.flash_ms") == {"name": "kanana2.flash_ms", "reader": "kernel_ms",
                                        "params": {"pattern": "flash_(fwd|dq|dkv)"}}
    new_readers = {"flash_roofline": "kanana2_flash_roofline", "mfu": "kanana2_mfu"}
    for name, reader in new_readers.items():
        assert load("kanana2." + name)["reader"] == reader
    man = json.load(open(os.path.join(bm_util.ROOT, "BENCHMARK.json")))
    mine = [m for m in man["per_layer"] if m["name"].startswith("kanana2.")]
    assert {m["name"] for m in mine} == {"kanana2." + n for n in (
        *twins, *new_readers, "mla_proj_ms", "mla_rope_ms", "flash_ms")}
    assert all(m["workloads"] == [NEW] for m in mine)
    first = man["per_layer"].index(mine[0])
    assert man["per_layer"][first:first + len(mine)] == mine      # appended as one run
    assert man["per_layer"][-1] == mine[-1] and man["workloads"][-1]["name"] == NEW
    listed = {m["name"] for m in man["end_to_end"] + man["per_layer"]
              if NEW in m.get("workloads", [])} - {m["name"] for m in mine}
    assert listed == {"train.samples_per_s", "step.inferred_phase_pct", "step.mixed_fusion_pct",
                      "lm.pack_ms"}
    mine_cell = man["workloads"][-1]
    assert (mine_cell["chips"], mine_cell["traffic"], mine_cell["config"]) == (
        1, "pretrain-8k", "kanana-2-30b-a3b")
    assert len(man["workloads"]) >= 8 and sum(w["chips"] == 4 for w in man["workloads"]) == 1
    assert len(man["per_layer"]) <= 128 and len(mine_cell["why"]) <= 200
    assert all(os.path.exists(os.path.join(BENCH_DIR, "metrics", m["name"] + ".json"))
               for m in man["per_layer"])
    # the scopes the metrics read are the program's, and the module metric reads the attention
    from apex_tpu.observability import phases
    assert set(scopes("kanana2.mla_proj_ms")[1] + scopes("kanana2.mla_rope_ms")[1]) <= set(
        phases.PHASES)
    import re
    want = re.compile(load("kanana2.mla_step_ms")["params"]["modules"])
    assert want.search("layers/3/self_attn") and want.search("layers/0/self_attn/k_up_proj")
    assert not want.search("layers/1/mlp") and not want.search("embed_tokens")
