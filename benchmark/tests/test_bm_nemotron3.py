"""The ``nemotron3`` cell's files on the CPU at the tiny configuration beside
these tests: the cell through ``runners/train_causal_lm`` and
``references/nemotron3`` (``runners/train_balanced_lm``: the selection bias
balanced on the seed's first batch) is ``correct``; a program whose scan leaves out the
state carried between chunks is not, where the test draws slow decays itself
(under the seed's own leaves, N(0, init_std), a chunk forgets what came before
it within a few positions and the skip ``D x`` drowns the scan); the reference
one precision down is not either; the reference is float32, ``highest``, free of
the program and scans step by step; the cut keeps every published width; the
closed-form FLOPs agree with ISSUE 44's count by hand; the new readers read a
made-up run and nothing where nothing is; every twin metric file equals its
twin's parameters."""

import json
import os
import types

import numpy as np

import bm_util

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "nemotron3-tiny.pretrain-lm-32"
NEW = "nemotron3-nano-30b-a3b.pretrain-8k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the catalog row's ``config``, but for the four keys the cut changes
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2, "head_dim": 128,
    "hidden_size": 2688, "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_hidden_act": "relu2", "model_type": "nemotron_h", "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1, "n_groups": 8,
    "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True, "residual_in_fp32": False,
    "rope_theta": 10000, "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False, "time_step_floor": 0.0001,
    "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1, "use_bias": False,
    "use_conv_bias": True, "use_mamba_kernels": True}


def _manifest():
    man = bm_util.manifest()
    man["workloads"].append({"name": CELL, "config": "nemotron3-tiny",
                             "traffic": "pretrain-lm-32", "chips": 4})
    return man


def _tiny_cfg():
    return json.load(open(os.path.join(bm_util.TINY, "configs", "nemotron3-tiny.json")))


def _cut():
    return json.load(open(os.path.join(BENCH_DIR, "configs", "nemotron3-nano-30b-a3b.json")))


def test_nemotron3_cell_is_correct_on_four_virtual_devices():
    result, lines = bm_util.run(CELL, seed=2**31 + 5, seconds=1.0, man=_manifest())
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 3
    compared = {l["compared"]: l for l in lines if "compared" in l}
    assert {"loss_gap", "grad_diff_mean", "update_norm_gap", "moe_dropped_assignments",
            "moe_held_shortfall", "replicas_differ"} <= set(compared)
    assert compared["moe_dropped_assignments"]["value"] == 0
    moe = next(l["moe"] for l in lines if "moe" in l)
    # 4 devices x 1 row x 32 tokens x 6 choices x 4 expert layers, half of them held
    assert 0 < moe["moe_assignments_held"] < 4 * 32 * 6 * 4 and moe["moe_expert_load_max"] > 0


def test_the_bias_is_balanced_on_the_first_batch_and_the_draw_is_handed_back():
    """``runners/train_balanced_lm.py`` at the tiny size: the family's rule on the
    seed's first batch brings the fullest expert of every layer from twice the
    mean and more to within a fifth of it, the program and the reference are
    handed one tree, and the benchmark's own draw is in place again after the
    run.  The balance is the seed's and plain code's: the runner names nothing
    of the program."""
    from lib import weights
    from references import nemotron3 as ref
    draw = weights.make_weights
    result, lines = bm_util.run(CELL, seed=21, seconds=0.2, man=_manifest())
    assert result["correct"] is True and weights.make_weights is draw
    said = next(l["bias_balanced"] for l in lines if "bias_balanced" in l)
    assert said["layers"] == ["1", "3", "6", "8"] and said["steps"] == ref.BALANCE["steps"]
    assert min(said["fullest_over_mean_before"]) > 1.6
    assert max(said["fullest_over_mean_after"]) < 1.25
    first = next(l["first_step"] for l in lines if "first_step" in l and "moe" in l)
    expected = 4 * 32 * 6 * 4 * 8 / 16
    assert abs(first["moe_assignments_held"] / expected - 1.0) < 0.06
    src = open(os.path.join(BENCH_DIR, "runners", "train_balanced_lm.py")).read()
    assert "apex_tpu" not in src and "self.run" not in src and "_route" not in src


def test_the_references_walk_balances_layer_after_layer_and_leaves_the_tree_alone():
    """``references/nemotron3.routing``: without ``balance`` the bias is the
    tree's and the loads are its loads; with it every layer's loads are even on
    the batch, each row sums to tokens x choices, and the same seed gives the
    same bias."""
    import jax
    from apex_tpu import models
    from lib import weights
    from references import nemotron3 as ref
    from runners.train_causal_lm import causal_lm_batch
    cfg = _tiny_cfg()
    model = models.NemotronH(models.NemotronHConfig.from_dict(cfg))
    shapes = jax.eval_shape(lambda k: model.init(k)[0], jax.random.PRNGKey(0))
    params = weights.make_weights(shapes, seed=7, std=cfg["init_std"])
    ids, = causal_lm_batch({"seq_len": 32}, 7, 0, 4, 64)
    walk = lambda balance: jax.device_get(jax.jit(
        lambda p, i: ref.routing(p, i, cfg, balance=balance))(params, ids))
    as_drawn, even, again = walk(False), walk(True), walk(True)
    assert [r["layer"] for r in even] == [1, 3, 6, 8]
    for drawn, row, twin in zip(as_drawn, even, again):
        tree = np.asarray(params["layers"][str(row["layer"])]["mlp"]["expert_bias"])
        assert np.array_equal(drawn["bias"], tree) and np.array_equal(drawn["loads"],
                                                                      drawn["drawn_loads"])
        assert np.array_equal(row["bias"], twin["bias"]) and not np.array_equal(row["bias"], tree)
        assert row["loads"].sum() == drawn["loads"].sum() == 4 * 32 * 6 and row["loads"].shape == (16,)
        assert row["loads"].max() / row["loads"].mean() < 1.25
    # the first expert layer reads the same stream either way; a later one reads what the
    # balanced earlier ones left
    assert np.array_equal(as_drawn[0]["loads"], even[0]["drawn_loads"])
    assert max(r["loads"].max() / r["loads"].mean() for r in as_drawn) > 1.6


def _slow_decays(params, seed):
    """The mixers' ``A_log`` and ``dt_bias`` as the family draws them and not as
    the seed does (A in [1, 16], delta in [0.001, 0.1]: a chunk of 8 forgets
    little), taps of torch's scale (ten times the seed's, so that x, B and C are
    not a hundredth of the stream) and no skip ``D x`` beside the scan: the state
    carried between chunks is then a tenth and more of what the mixer returns."""
    import jax
    import jax.numpy as jnp
    out = jax.tree_util.tree_map(lambda x: x, params)
    for i, layer in out["layers"].items():
        if "mamba" not in layer:
            continue
        ka, kd = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(seed), int(i)))
        m = dict(layer["mamba"])
        heads = m["A_log"].shape
        m["A_log"] = jnp.log(jax.random.uniform(ka, heads, jnp.float32, 1.0, 16.0))
        dt = jnp.exp(jax.random.uniform(kd, heads, jnp.float32, np.log(1e-3), np.log(1e-1)))
        m["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))
        m["D"] = jnp.zeros(heads, jnp.float32)
        m["conv1d"] = {**m["conv1d"], "weight": 10.0 * m["conv1d"]["weight"]}
        out["layers"][i] = {**layer, "mamba": m}
    return out


def test_a_scan_without_its_carried_state_is_not_correct_where_the_decays_are_slow(monkeypatch):
    """The runner at the tiny size with slow decays drawn by the test: sound, it
    reads ``correct`` true; with the state carried between chunks left out of the
    program's scan (every chunk starts from zeros), false, by the gradient's
    difference."""
    import jax.numpy as jnp
    from apex_tpu.transformer import mamba2
    from lib import weights
    real_make = weights.make_weights
    monkeypatch.setattr(weights, "make_weights",
                        lambda shapes, seed, *a, **k: _slow_decays(real_make(shapes, seed, *a, **k),
                                                                   seed))
    sound, lines = bm_util.run(CELL, seed=11, seconds=0.3, man=_manifest())
    assert sound["correct"] is True
    read = lambda ls, name: next(l["value"] for l in ls if l.get("compared") == name)
    real_scan = mamba2.ssd_chunked

    def chunks_alone(x, dt, A, B, C, D, chunk):
        b, T, H, P = x.shape
        cut = lambda a: a.reshape(b * (T // chunk), chunk, *a.shape[2:])
        return real_scan(cut(x), cut(dt), A, cut(B), cut(C), D, chunk).reshape(b, T, H, P)

    monkeypatch.setattr(mamba2, "ssd_chunked", chunks_alone)
    broken, broken_lines = bm_util.run(CELL, seed=11, seconds=0.3, man=_manifest())
    assert broken["correct"] is False
    limit = _tiny_cfg()["limits"]["grad_diff_mean"]
    # read: 0.29 without the carried state for 0.07 with it (a balanced bias leaves many
    # experts a rounding away from the choice, so a sound bf16 run differs by more than the
    # 0.03 it reads under a random bias)
    assert read(broken_lines, "grad_diff_mean") > 2 * limit
    assert limit > 1.5 * read(lines, "grad_diff_mean")


def test_controls_fail_where_the_stated_precision_passes():
    """At a size a test can hold, relatively (the limits in
    references/nemotron3.py are the chip-size cell's): fp8-rounded matmuls move
    the first gradient at least twice as far as bf16 ones, and a bf16 parameter
    store breaks the limit that is there for it."""
    import jax
    from apex_tpu import models
    from lib import weights
    from references import nemotron3 as ref
    from runners.train_causal_lm import causal_lm_batch
    cfg = _tiny_cfg()
    limits = cfg["limits"]
    model = models.NemotronH(models.NemotronHConfig.from_dict(cfg))
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
    assert set(ref.LIMITS) == {"loss_gap", "grad_diff_mean", "update_norm_gap"}


def test_reference_is_float32_highest_free_of_the_program_and_scans_step_by_step():
    src = open(os.path.join(BENCH_DIR, "references", "nemotron3.py")).read()
    assert "import apex_tpu" not in src and "from apex_tpu" not in src
    assert "jax.lax.scan(step, S, inputs)" in src and "jax.checkpoint" in src
    assert "cumsum" not in src                          # no chunked form in the reference
    assert "Precision.HIGHEST" in open(os.path.join(BENCH_DIR, "references",
                                                    "_precision.py")).read()


def test_the_cut_keeps_every_published_width_and_the_readers_keys():
    cfg = _cut()
    for key, value in PUBLISHED.items():
        assert cfg[key] == value, key
    if os.path.exists(CATALOG):             # the catalog beside the guide, where it is installed
        row = next(json.loads(l) for l in open(CATALOG)
                   if '"name": "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"' in l)
        assert {k: v for k, v in row["config"].items() if k not in cfg["reduced"]} == PUBLISHED
        assert row["source_url"] in cfg["source"]
        assert cfg["published"]["hybrid_override_pattern"] == row["config"][
            "hybrid_override_pattern"]
    assert cfg["reduced"] == ["num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
                              "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["hybrid_override_pattern"]) == (9, "MEMEM*EME")
    assert cfg["published"]["hybrid_override_pattern"].startswith(cfg["hybrid_override_pattern"])
    assert cfg["n_routed_experts"] == cfg["num_experts"] == 8 and cfg["experts_held_start"] == 0
    assert cfg["num_experts_published"] == cfg["published"]["n_routed_experts"] == 128
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"] == 131072
    assert cfg["published"]["num_hidden_layers"] == 52
    man = json.load(open(os.path.join(bm_util.ROOT, "BENCHMARK.json")))
    entry = next(c for c in man["configs"] if c["name"] == "nemotron3-nano-30b-a3b")
    assert entry["reduced"] == cfg["reduced"] and entry["source"] in cfg["source"]
    # what the accepted runner and readers look for, stated as derived
    assert cfg["layer_types"] == ["mamba", "moe", "mamba", "moe", "mamba", "full_attention", "moe",
                                  "mamba", "moe"]
    assert cfg["mlp_layer_types"].count("sparse") == 4 and set(cfg["derived"]) >= {
        "layer_types", "mlp_layer_types", "num_experts", "conv_L_cache", "rms_norm_eps"}
    assert cfg["runner"] == "train_balanced_lm" and cfg["reference"] == "nemotron3"
    # one learning rate in the example's argv, in the reference's Adam and in the tiny file
    from references import nemotron3 as ref
    lr = cfg["argv"][cfg["argv"].index("--lr") + 1]
    assert float(lr) == ref.ADAM["lr"] == 1e-6 and _tiny_cfg()["argv"].count(lr) == 1
    assert {k: v for k, v in ref.ADAM.items() if k != "lr"} == {
        "beta1": 0.9, "beta2": 0.999, "eps": 1e-8, "weight_decay": 0.01}
    assert cfg["per_chip_batch"] == 1 and cfg["moe_row_buffer_factor"] == 2.0
    assert cfg["head_chunk"] == 4096 and "limits" not in cfg and cfg["window_steps"] % 8 == 0
    assumed = cfg["assumed"]
    assert {"d_in", "time_step_limit", "attention_positions", "router", "router_aux_loss",
            "float32_leaves", "expert_bias", "init_std", "remat", "planned_bytes", "window_steps",
            "optimizer", "per_chip_batch", "moe_row_buffer_factor", "head_chunk"} <= set(assumed)
    assert set(assumed["planned_bytes"]) >= {"remat_nothing", "remat_dots", "remat_none"}
    # the parameters at the floors' own shape: 666.96 M
    d, d_in, conv = 2688, 4096, 4096 + 2 * 8 * 128
    mamba = d * (2 * d_in + 2 * 8 * 128 + 64) + d_in * d + 4 * conv + conv + 3 * 64 + d_in + d
    attn = d * (32 * 128 + 2 * 2 * 128) + 32 * 128 * d + d
    moe = 8 * 2 * d * 1856 + 2 * d * 3712 + d * 128 + 128 + d
    assert 4 * mamba + attn + 4 * moe + 2 * 16384 * d + d == 666_963_456


def test_closed_form_flops_of_the_published_cut():
    from lib import nemotron3_flops as nf, peaks as pk
    cfg = _cut()
    held = 4 * 8192 * 6 * 8 / 128               # a uniform routing's share, 4 expert layers
    parts = nf.forward_flops_per_seq(cfg, 8192, held)
    per_token = {k: v / 8192 / 1e6 for k, v in parts.items()}
    # ISSUE 44's hand count, MFLOP a token: a Mamba-2 block 77.4 in its two projections and
    # 3.4 in the chunked scan's products; the attention block 46.8 and 67.1; an expert block
    # 39.9 shared, 0.7 router, 7.5 routed; the head 88
    assert abs(per_token["mamba_projections"] / 4 - 77.41) < 0.01
    assert abs(per_token["mamba_scan"] / 4 - 3.42) < 0.01
    assert abs(per_token["attention_projections"] - 46.79) < 0.01
    assert abs(per_token["attention_scores"] - 67.12) < 0.01
    assert abs(per_token["shared_expert"] / 4 - 39.91) < 0.01
    assert abs(per_token["router"] / 4 - 0.688) < 0.001
    assert abs(per_token["routed_experts"] / 4 - 7.48) < 0.01
    assert abs(per_token["head"] - 88.07) < 0.02
    assert abs(sum(per_token.values()) - 719.0) < 1.5
    assert abs(nf.train_flops_per_seq(cfg, 8192, held) / 1e12 - 17.67) < 0.05
    # the scan's least time at the cell's shape is the memory's, not the matrix unit's
    fl, by = nf.scan_forward_flops_bytes(cfg, 1, 8192)
    assert by == 8192 * (2 * (2 * 4096 + 2 * 1024) + 4 * 64)
    peaks = pk.peaks_for("TPU v5 lite")
    assert fl / peaks["bf16_flops"] < by / peaks["hbm_bytes_per_s"]
    every, every_bytes = nf.scan_train_flops_bytes(cfg, 1, 8192, forward_calls=2.0)
    assert every == 4 * 4.0 * fl and every_bytes == 4 * 4.0 * by


def test_the_new_readers_read_a_made_up_run_and_nothing_where_nothing_is(monkeypatch):
    from lib import grouped_dot as gd, nemotron3_flops as nf, peaks as pk, phase_table as pt
    from readers import nemotron3_grouped_dot_roofline as gdr, nemotron3_mfu, nemotron3_ssd_roofline
    cfg, peaks = _cut(), pk.peaks_for("TPU v5 lite")
    empty = types.SimpleNamespace(cell=None, facts={}, spans=[], trace=None, ops={}, stretch=None,
                                  iterations=0, peaks=None)
    assert nemotron3_mfu.read(empty) is None
    # a program without the ledger's text or the scope (the parent) reads nothing, and does not raise
    assert nemotron3_ssd_roofline.read(empty, "lm.train_step") is None
    assert gdr.read(empty, "lm.train_step") is None
    held = 4 * 3072.0
    facts = {"model": cfg, "tokens_per_step": 8192, "rows_per_step": 1, "seq_len": 8192,
             "moe_assignments_held": held}
    cell = types.SimpleNamespace(chips=1)
    ctx = types.SimpleNamespace(cell=cell, facts=facts, stretch=(0.0, 0.9e9), iterations=3,
                                peaks=peaks, ops={0: []})
    need = nf.train_flops_per_seq(cfg, 8192, held)
    got = nemotron3_mfu.read(ctx)
    assert abs(got["value"] - 100.0 * need / 0.3 / peaks["bf16_flops"]) < 1e-9 and got["value"] < 100
    assert abs(sum(got["forward_share_by_part"].values()) - 1.0) < 1e-9
    other = dict(facts, model={k: v for k, v in cfg.items() if k != "hybrid_override_pattern"})
    assert nemotron3_mfu.read(types.SimpleNamespace(**{**vars(ctx), "facts": other})) is None

    # the scan: forward, replayed and backward operations under the scope, 3 iterations
    fl, by = nf.scan_train_flops_bytes(cfg, 1, 8192, forward_calls=2.0)
    least_ns = max(fl / peaks["bf16_flops"], by / peaks["hbm_bytes_per_s"]) * 1e9
    scan = ("model", "layers/0/mamba", "mamba.scan")
    ev = lambda name, ns, kind="fusion": [f"%{name} = f32[] {kind}()", 0.0, ns, {"kind": kind}]
    rows = [(ev("fusion.1", 3 * least_ns), scan, False, pt.TEXT),
            (ev("fusion.2", 3 * least_ns), scan, True, pt.TEXT),
            (ev("fusion.3", 6 * least_ns), scan, True, pt.TEXT),
            (ev("fusion.9", 5e6), ("model", "layers/0/mamba", "mamba.conv"), False, pt.TEXT)]
    monkeypatch.setattr(pt, "rows_by_chip", lambda ctx, entry: {0: rows})
    monkeypatch.setattr(nemotron3_ssd_roofline, "replayed_instructions",
                        lambda entry, scope: {"fusion.2"})
    share = nemotron3_ssd_roofline.read(ctx, "lm.train_step")
    assert abs(share["value"] - 25.0) < 1e-6 and share["forward_calls"] == 2.0
    assert share["bound"] == "memory"
    by_pass = share["ms_by_pass"]
    assert abs(by_pass["backward"] - 2 * by_pass["forward"]) < 1e-9
    assert abs(by_pass["replayed"] - by_pass["forward"]) < 1e-9
    monkeypatch.setattr(nemotron3_ssd_roofline, "replayed_instructions", lambda entry, scope: set())
    assert nemotron3_ssd_roofline.read(ctx, "lm.train_step")["forward_calls"] == 1.0

    # the grouped products: 2 + 4 a layer, + 2 under a remat that recomputes
    remat = bool(cfg.get("remat"))
    per_layer = 6 + (2 if remat else 0)
    experts = ("model", "layers/1/mlp", "moe.experts")
    flops, moved = gd.product_flops_bytes(3072.0, 2688, 1856, 8)
    least_ns = max(flops / peaks["bf16_flops"], moved / peaks["hbm_bytes_per_s"]) * 1e9
    kernels = [(ev(f"grouped_rows.{i}", 2 * least_ns, "custom-call"), experts, i % 2 == 1, pt.TEXT)
               for i in range(3 * 4 * per_layer)]
    shared = [(ev("fusion.70", 9e6), experts, False, pt.TEXT)]
    monkeypatch.setattr(pt, "rows_by_chip", lambda ctx, entry: {0: kernels + shared})
    got = gdr.read(ctx, "lm.train_step")
    assert abs(got["value"] - 50.0) < 1e-6 and got["products_per_layer"] == per_layer
    assert got["kernels_per_layer"] == per_layer and got["rows_per_layer"] == 3072.0
    # a count a layer that is not the products': the time and the operations are of other work
    monkeypatch.setattr(pt, "rows_by_chip", lambda ctx, entry: {0: kernels[:-3] + shared})
    assert gdr.read(ctx, "lm.train_step") is None
    # no kernel under the scope (lax.ragged_dot as XLA's own fusions): the whole scope's time
    monkeypatch.setattr(pt, "rows_by_chip", lambda ctx, entry: {0: shared})
    assert gdr.read(ctx, "lm.train_step")["kernels_per_layer"] == 0


def test_every_twin_metric_file_points_at_an_accepted_reader_with_its_twins_parameters():
    twins = {"moe_step_ms": "lfm2.moe_step_ms", "moe_dispatch_ms": "lfm2.moe_dispatch_ms",
             "moe_expert_load_max": "lfm2.moe_expert_load_max", "attn_step_ms": "lfm2.attn_step_ms",
             "flash_roofline": "lfm2.flash_roofline", "amp_step_ms": "lm.amp_step_ms",
             "optimizer_ms": "lm.optimizer_ms", "device_idle": "lm.device_idle",
             "unscoped_pct": "lm.unscoped_pct", "import_s": "lm.import_s",
             "model_init_s": "lm.model_init_s", "step_trace_s": "lm.step_trace_s",
             "step_load_s": "lm.step_load_s"}
    load = lambda name: json.load(open(os.path.join(BENCH_DIR, "metrics", name + ".json")))
    for new, old in twins.items():
        mine, theirs = load("nemotron3." + new), load(old)
        assert mine["name"] == "nemotron3." + new
        assert (mine["reader"], mine.get("params")) == (theirs["reader"], theirs.get("params")), new
    adam, theirs = load("nemotron3.adam_roofline"), load("lm.adam_roofline")
    assert adam["reader"] == theirs["reader"] == "adam_roofline"
    assert adam["params"] == {**theirs.get("params", {}), "grad_bytes": 2}
    attn, mamba = load("lfm2.attn_step_ms"), load("nemotron3.mamba_step_ms")
    assert mamba["reader"] == attn["reader"] == "module_ms"
    assert {**mamba["params"], "modules": None} == {**attn["params"], "modules": None}
    assert load("nemotron3.ssd_ms") == {"name": "nemotron3.ssd_ms", "reader": "phase_ms",
                                        "params": {"entry": "lm.train_step",
                                                   "within": ["mamba.scan"]}}
    new_readers = {"ssd_roofline": "nemotron3_ssd_roofline", "mfu": "nemotron3_mfu",
                   "grouped_dot_roofline": "nemotron3_grouped_dot_roofline"}
    for name, reader in new_readers.items():
        assert load("nemotron3." + name)["reader"] == reader
    man = json.load(open(os.path.join(bm_util.ROOT, "BENCHMARK.json")))
    mine = [m for m in man["per_layer"] if m["name"].startswith("nemotron3.")]
    assert {m["name"] for m in mine} == {"nemotron3." + n for n in (
        *twins, *new_readers, "adam_roofline", "mamba_step_ms", "ssd_ms")}
    assert all(m["workloads"] == [NEW] for m in mine)
    first = man["per_layer"].index(mine[0])
    assert man["per_layer"][first:first + len(mine)] == mine      # appended as one run
    listed = {m["name"] for m in man["end_to_end"] + man["per_layer"]
              if NEW in m.get("workloads", [])} - {m["name"] for m in mine}
    assert listed == {"train.samples_per_s", "step.inferred_phase_pct", "step.mixed_fusion_pct",
                      "lm.pack_ms"}
    mine_cell = next(w for w in man["workloads"] if w["name"] == NEW)
    assert (mine_cell["chips"], mine_cell["traffic"]) == (1, "pretrain-8k")
    # (a count that a later cell does not break: test_bm_ouro.py pins six and fails since this one)
    assert len(man["workloads"]) >= 7 and sum(w["chips"] == 4 for w in man["workloads"]) == 1
    assert all(os.path.exists(os.path.join(BENCH_DIR, "metrics", m["name"] + ".json"))
               for m in man["per_layer"])


def test_the_mamba_metric_reads_the_mixers_and_nothing_else():
    import re
    want = re.compile(json.load(open(os.path.join(
        BENCH_DIR, "metrics", "nemotron3.mamba_step_ms.json")))["params"]["modules"])
    for module in ("layers/0/mamba", "layers/7/mamba/in_proj", "layers/2/mamba/out_proj"):
        assert want.search(module), module
    for module in ("layers/1/mlp", "layers/5/self_attn/q_proj", "embed_tokens", "norm",
                   "layers/0/input_layernorm"):
        assert not want.search(module), module
