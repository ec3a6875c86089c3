"""The phase vocabulary inside the program: every scope of
``observability.phases.PHASES`` names instructions of a compiled step, the
module path tells forward from backward, kernel names stay what the
benchmark's readers match on, ``instruction_phases`` reads v5e HLO text,
the ledger hands out the compiled text and the stage times of a tracing
dispatch and a steady dispatch stores nothing, and ``build()`` leaves its
set-up spans on a recorder whose origin can be read."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import pytest

from apex_tpu import amp, models, optimizers, serving
from apex_tpu.observability import compilation as C, get_recorder, phases
from apex_tpu.observability.tracing import SpanRecorder

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRAIN_SCOPES = ("amp.scale_loss", "amp.pack", "amp.unscale", "amp.scaler_update",
                "amp.update", "amp.rebuild", "optim.adam", "model", "loss")
DDP_SCOPES = ("ddp.pack", "ddp.reduce", "ddp.unpack")
BUILD_SPANS = ("build.amp_initialize", "build.model_init", "build.place_params",
               "build.optimizer_init", "build.step_wrap")


def _bert_example():
    spec = importlib.util.spec_from_file_location(
        "bert_main_amp", os.path.join(ROOT, "examples", "bert", "main_amp.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _build(argv, devices=None):
    """The example's own build(), on all virtual devices or on the first n."""
    mod, real = _bert_example(), jax.devices
    if devices:
        jax.devices = lambda *a, **k: real()[:devices]
    try:
        return mod.build(mod.parse_args(["--config", "tiny", "-b", "2", "--seq-len", "32",
                                         *argv]))
    finally:
        jax.devices = real


def _scopes(text):
    found = set()
    for path, _ in phases.instruction_phases(text).values():
        found.update(p for p in path if p in phases.PHASES)
    return found


@pytest.fixture(scope="module")
def programs():
    """scope group -> (set of scopes in the compiled program, phase map)."""
    out = {}
    prev = C.set_ledger(C.CompilationLedger())
    try:
        recorder = get_recorder()
        already = len(recorder.events())
        mesh = _build([])
        out["spans"] = (recorder.origin, recorder.events()[already:])
        before = json.dumps(C.get_ledger().snapshot()["entries"], default=repr)
        assert "bert.train_step" not in before
        state, metrics = mesh.train_step(mesh.state, mesh.put_batch(mesh.get_batch(0)))
        first = json.dumps(C.get_ledger().snapshot()["entries"]["bert.train_step"],
                           default=repr, sort_keys=True)
        state, metrics = mesh.train_step(state, mesh.put_batch(mesh.get_batch(1)))
        jax.block_until_ready(metrics)
        second = json.dumps(C.get_ledger().snapshot()["entries"]["bert.train_step"],
                            default=repr, sort_keys=True)
        text = C.get_ledger().compiled_text("bert.train_step")
        after = json.dumps(C.get_ledger().snapshot()["entries"]["bert.train_step"],
                           default=repr, sort_keys=True)
        out["ledger"] = {"first": first, "second": second, "after_text": after,
                         "text": text, "again": C.get_ledger().compiled_text("bert.train_step"),
                         "traces": C.get_ledger().total_traces()}
        out["mesh"] = (_scopes(text), phases.instruction_phases(text))

        one = _build([], devices=1)
        assert one.ndev == 1
        text = one.train_step.lower(one.state, one.put_batch(one.get_batch(0))).compile().as_text()
        out["one"] = (_scopes(text), None)

        lamb = _build(["--optimizer", "lamb"], devices=1)
        text = lamb.train_step.lower(lamb.state,
                                     lamb.put_batch(lamb.get_batch(0))).compile().as_text()
        out["lamb"] = (_scopes(text), None)

        # FusedLion under amp, with the grad norm consumed (the BERT step drops it)
        model, opt = amp.initialize(models.BertForPretraining(models.BertConfig(
            vocab_size=64, hidden_size=16, num_hidden_layers=1, num_attention_heads=2,
            intermediate_size=32)), optimizers.FusedLion(lr=1e-4), opt_level="O2", verbosity=0)
        params, _ = model.init(jax.random.PRNGKey(0))
        opt_state = opt.init(params)

        def lion_step(params, opt_state, ids):
            loss, grads = amp.scaled_grad(
                lambda p: jnp.mean(model.apply(p, ids)[0][0].astype(jnp.float32) ** 2),
                params, opt_state)
            params, opt_state, info = opt.step(params, opt_state, grads)
            return params, opt_state, loss, info["grad_norm"]
        text = jax.jit(lion_step).lower(params, opt_state,
                                        jnp.ones((2, 8), jnp.int32)).compile().as_text()
        out["lion"] = (_scopes(text), None)

        gpt = models.GPT(models.GPTConfig(vocab_size=64, block_size=32, n_layer=1, n_head=2,
                                          n_embd=16))
        gparams, _ = gpt.init(jax.random.PRNGKey(1))
        eng = serving.PagedEngine(gpt, gparams, slots=2, buf_len=16, block_size=8,
                                  prefill_chunk=8, window=2)
        eng.warmup()
        out["paged"] = (_scopes(C.get_ledger().compiled_text("engine._paged_step_k")), None)

        # the per-layer decoder with routed experts, its blocks rematerialized
        lag = models.Laguna(models.LagunaConfig(
            vocab_size=64, hidden_size=16, intermediate_size=32,
            layer_types=["full_attention", "sliding_attention"],
            num_attention_heads_per_layer=[2, 4], mlp_layer_types=["dense", "sparse"],
            num_key_value_heads=2, head_dim=8, sliding_window=4, num_experts=4,
            num_experts_per_tok=2, moe_intermediate_size=8, shared_expert_intermediate_size=8,
            rope_parameters={k: {"rope_theta": 10000.0}
                             for k in ("full_attention", "sliding_attention")},
            remat="dots", head_chunk=32))
        lparams, _ = lag.init(jax.random.PRNGKey(2))
        text = jax.jit(jax.grad(lambda p: lag.loss(p, jnp.ones((1, 16), jnp.int32)))).lower(
            lparams).compile().as_text()
        out["moe"] = (_scopes(text), phases.instruction_phases(text))

        # the same decoder with a gated short-convolution layer and QK-norm
        mix = models.Laguna(models.LagunaConfig(
            vocab_size=64, hidden_size=16, intermediate_size=32,
            layer_types=["conv", "full_attention"], num_attention_heads_per_layer=[2, 2],
            mlp_layer_types=["dense", "dense"], num_key_value_heads=2, head_dim=8,
            sliding_window=None, num_experts=4, num_experts_per_tok=2, moe_intermediate_size=8,
            rope_parameters={"full_attention": {"rope_theta": 10000.0}}, qk_norm=True,
            tie_word_embeddings=True, head_chunk=32))
        mparams, _ = mix.init(jax.random.PRNGKey(3))
        text = jax.jit(jax.grad(lambda p: mix.loss(p, jnp.ones((1, 16), jnp.int32)))).lower(
            mparams).compile().as_text()
        out["conv"] = (_scopes(text), phases.instruction_phases(text))

        # the same decoder with its stack looped: one scan over the passes, an exit gate
        loop = models.Laguna(models.LagunaConfig(
            vocab_size=64, hidden_size=16, intermediate_size=32,
            layer_types=["full_attention"] * 2, num_attention_heads_per_layer=[2, 2],
            mlp_layer_types=["dense", "dense"], num_key_value_heads=2, head_dim=8,
            sliding_window=None, rope_parameters={"full_attention": {"rope_theta": 10000.0}},
            gating=False, total_ut_steps=3, sandwich_norm=True, exit_beta=0.05,
            remat="nothing", head_chunk=32))
        oparams, _ = loop.init(jax.random.PRNGKey(4))
        text = jax.jit(jax.grad(lambda p: loop.loss(p, jnp.ones((1, 16), jnp.int32)))).lower(
            oparams).compile().as_text()
        out["loop"] = (_scopes(text), phases.instruction_phases(text))

        # a one-branch decoder of Mamba-2 mixers (models/nemotron_h.py)
        ssm = models.NemotronH(models.NemotronHConfig(
            vocab_size=64, hidden_size=16, hybrid_override_pattern="MM", mamba_num_heads=2,
            mamba_head_dim=8, ssm_state_size=8, n_groups=1, num_attention_heads=2,
            num_key_value_heads=2, head_dim=8, chunk_size=8, head_chunk=32))
        sparams, _ = ssm.init(jax.random.PRNGKey(5))
        text = jax.jit(jax.grad(lambda p: ssm.loss(p, jnp.ones((1, 16), jnp.int32)))).lower(
            sparams).compile().as_text()
        out["mamba"] = (_scopes(text), phases.instruction_phases(text))

        # the per-layer decoder with latent attention (models/deepseek_v3.py)
        lat = models.DeepseekV3(models.DeepseekV3Config.from_dict(dict(
            vocab_size=64, hidden_size=16, intermediate_size=32, num_hidden_layers=1,
            first_k_dense_replace=1, num_attention_heads=2, qk_nope_head_dim=8,
            qk_rope_head_dim=4, v_head_dim=8, kv_lora_rank=8, rope_theta=10000.0, head_chunk=32)))
        aparams, _ = lat.init(jax.random.PRNGKey(6))
        text = jax.jit(jax.grad(lambda p: lat.loss(p, jnp.ones((1, 16), jnp.int32)))).lower(
            aparams).compile().as_text()
        out["mla"] = (_scopes(text), phases.instruction_phases(text))
    finally:
        C.set_ledger(prev)
    return out


CASES = ([("mesh", s) for s in TRAIN_SCOPES + DDP_SCOPES]
         + [("one", s) for s in TRAIN_SCOPES]
         + [("lamb", "optim.lamb"), ("lion", "optim.lion"), ("lion", "amp.grad_norm"),
            ("paged", "paged.gather"), ("paged", "paged.scatter"), ("paged", "paged.attend"),
            ("moe", "moe.route"), ("moe", "moe.dispatch"), ("moe", "moe.experts"),
            ("moe", "moe.combine"),
            ("conv", "conv.in_proj"), ("conv", "conv.mix"), ("conv", "conv.out_proj"),
            ("conv", "attn.qk_norm"),
            ("loop", "loop"), ("loop", "loop.norm"), ("loop", "loss.head"), ("loop", "loss.exit"),
            ("mamba", "mamba.in_proj"), ("mamba", "mamba.conv"), ("mamba", "mamba.scan"),
            ("mamba", "mamba.gate_norm"), ("mamba", "mamba.out_proj"),
            ("mla", "mla.q_proj"), ("mla", "mla.kv_down"), ("mla", "mla.kv_norm"),
            ("mla", "mla.kv_up"), ("mla", "mla.rope"), ("mla", "mla.o_proj")])


def test_every_scope_of_the_vocabulary_has_a_case():
    assert {s for _, s in CASES} == set(phases.PHASES)
    assert len(set(phases.PHASES)) == len(phases.PHASES)


@pytest.mark.parametrize("program,scope", CASES)
def test_scope_names_instructions_of_the_compiled_step(programs, program, scope):
    assert scope in programs[program][0]


@pytest.mark.parametrize("module", ["bert/0/attention/qkv", "bert/1/intermediate",
                                    "bert/0/output_ln", "mlm_dense"])
def test_forward_and_backward_of_a_module_are_told_apart(programs, module):
    directions = {backward for path, backward in programs["mesh"][1].values()
                  if "model" in path and path[path.index("model") + 1:][:1]
                  == ("BertForPretraining/" + module,)}
    assert directions == {False, True}


def test_a_looped_stacks_modules_stay_next_to_model_with_the_loop_after_them(programs):
    """The scope around the scan comes before the module paths in an
    ``op_name`` and after them in a phase, forward and backward, so that a
    reader by module finds the looped blocks as it finds any other."""
    found = {(path, backward) for path, backward in programs["loop"][1].values()}
    for module in ("layers/0/self_attn/q_proj", "layers/1/mlp/down_proj",
                   "layers/1/post_attention_layernorm_2"):
        assert {b for path, b in found if path[:3] == ("model", module, "loop")} == {False, True}
    assert (("model", "norm", "loop", "loop.norm"), False) in found
    assert any(path == ("model", "loop") for path, _ in found)          # the scan's own work
    assert any(path[:2] == ("loss", "loss.head") for path, _ in found)
    assert any(path[:2] == ("loss", "loss.exit") and b for path, b in found)


def test_nested_scopes_come_outermost_first(programs):
    paths = {path for path, _ in programs["mesh"][1].values()}
    assert ("amp.update", "optim.adam") in paths and ("amp.update", "amp.rebuild") in paths
    assert not any(path[:1] == ("optim.adam",) for path in paths)


@pytest.mark.parametrize("op_name,expected", [
    ("jit(step)/shard_map/amp.update/cond/branch_0_fun/optim.adam/mul",
     (("amp.update", "optim.adam"), False)),
    ("jit(step)/shard_map/transpose(jvp(model))/BertForPretraining/bert/3/attention/qkv/"
     "dot_general", (("model", "BertForPretraining/bert/3/attention/qkv"), True)),
    ("jit(step)/jvp(model)/BertForPretraining/bert/word_embeddings/jit(_take)/gather",
     (("model", "BertForPretraining/bert/word_embeddings"), False)),
    ("jit(step)/shard_map/jvp(model)/BertForPretraining/bert/2/attention/custom_vjp_call/"
     "pallas_call", (("model", "BertForPretraining/bert/2/attention"), False)),
    ("jit(step)/transpose(jvp(model))/BertForPretraining/bert/embeddings_ln/jit(_bwd)/"
     "layer_norm_bwd/pallas_call", (("model", "BertForPretraining/bert/embeddings_ln"), True)),
    ("transpose(jvp(loss))/jit(log_softmax)/sub", (("loss",), True)),
    ("jit(step)/shard_map/jvp(amp.scale_loss)/mul", (("amp.scale_loss",), False)),
    ("jit(step)/shard_map/div", ((), False)),
    ("jit(_paged_step_k)/while/body/cond/branch_1_fun/paged.gather/gather",
     (("paged.gather",), False)),
])
def test_phase_of_op_name(op_name, expected):
    assert phases.phase_of_op_name(op_name) == expected


# a snippet of the optimized text of bert-large's step as the v5e compiler
# prints it (PR 24: names and metadata as recorded, shapes and configs cut)
V5E_TEXT = '''
HloModule jit_step, is_scheduled=true

%fused_computation.1178 (param_0.2898: bf16[512,1024]) -> f32[64,8,8,128] {
  %param_0.2898 = bf16[512,1024]{1,0:T(8,128)(2,1)S(1)} parameter(0)
  %convert.359 = f32[512,1024]{1,0:T(8,128)} convert(%param_0.2898), metadata={op_name="jit(step)/amp.pack/convert_element_type" stack_frame_id=3}
  ROOT %bitcast.2620 = f32[64,8,8,128]{3,2,1,0:T(8,128)S(1)} bitcast(%convert.359)
}

%fused_computation.1472 (param_0.3316: f32[1024]) -> f32[265572352] {
  %custom-call.13 = f32[265572352]{0:T(1024)} custom-call(), custom_call_target="AllocateBuffer"
  %param_0.3316 = f32[1024]{0:T(1024)} parameter(0)
  %constant.1442 = s32[] constant(0)
  ROOT %dynamic-update-slice.511 = f32[265572352]{0:T(1024)} dynamic-update-slice(%custom-call.13, %param_0.3316, %constant.1442)
}

%region_222.250 (arg_tuple.1: (f32[265572352], f32[265572352])) -> (f32[265572352]) {
  %arg_tuple.1 = (f32[265572352]{0:T(1024)}, f32[265572352]{0:T(1024)}) parameter(0)
  %get-tuple-element.1 = f32[265572352]{0:T(1024)} get-tuple-element(%arg_tuple.1), index=0
  %_adam_flat.1 = (f32[2074784,128]{1,0:T(8,128)}) custom-call(%get-tuple-element.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/amp.update/cond/branch_0_fun/optim.adam/jit(_adam_flat)/pallas_call" stack_frame_id=9}
  %copy-start.7 = (f32[265572352]{0:T(1024)}, f32[265572352]{0:T(1024)}, u32[]{:S(2)}) copy-start(%get-tuple-element.1)
  %copy-done.7 = f32[265572352]{0:T(1024)} copy-done(%copy-start.7)
  %slice.40 = bf16[1048576]{0:T(1024)(128)(2,1)} slice(%copy-done.7), slice={[0:1048576]}, metadata={op_name="jit(step)/amp.update/cond/branch_0_fun/amp.rebuild/dynamic_slice" stack_frame_id=11}
  ROOT %tuple.9 = (f32[265572352]{0:T(1024)}) tuple(%copy-done.7)
}

%region_223.251 (arg_tuple.2: (f32[265572352], f32[265572352])) -> (f32[265572352]) {
  %arg_tuple.2 = (f32[265572352]{0:T(1024)}, f32[265572352]{0:T(1024)}) parameter(0)
  %get-tuple-element.2 = f32[265572352]{0:T(1024)} get-tuple-element(%arg_tuple.2), index=0
  ROOT %tuple.10 = (f32[265572352]{0:T(1024)}) tuple(%get-tuple-element.2)
}

ENTRY %main.253 (args_0: bf16[512,1024], args_1: f32[1024]) -> (f32[265572352]) {
  %args_0 = bf16[512,1024]{1,0:T(8,128)(2,1)} parameter(0), metadata={op_name="args[0]"}
  %args_1 = f32[1024]{0:T(1024)} parameter(1)
  %flash_fwd.3 = (bf16[8,16,512,64]{3,2,1,0:T(8,128)(2,1)}) custom-call(%args_0), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(model)/BertForPretraining/bert/3/attention/jit(_fwd)/flash_fwd/pallas_call" stack_frame_id=30}
  %fusion.431 = bf16[4096,1024]{1,0:T(8,128)(2,1)} fusion(%args_0), kind=kOutput, calls=%fused_computation.1178, metadata={op_name="jit(step)/shard_map/transpose(jvp(model))/BertForPretraining/bert/3/intermediate/dot_general" stack_frame_id=41}
  %convert_bitcast_fusion.98 = f32[64,8,8,128]{3,2,1,0:T(8,128)S(1)} fusion(%fusion.431), kind=kLoop, calls=%fused_computation.1178
  %constant_dynamic-update-slice_fusion.234 = f32[265572352]{0:T(1024)} fusion(%args_1), kind=kLoop, calls=%fused_computation.1472
  %copy.2711 = f32[1024]{0:T(1024)S(1)} copy(%args_1)
  %conditional.1 = (f32[265572352]{0:T(1024)}) conditional(%args_1, %constant_dynamic-update-slice_fusion.234, %constant_dynamic-update-slice_fusion.234), branch_computations={%region_222.250, %region_223.251}, metadata={op_name="jit(step)/shard_map/amp.update/cond" stack_frame_id=8}
  ROOT %get-tuple-element.9 = f32[265572352]{0:T(1024)} get-tuple-element(%conditional.1), index=0, metadata={op_name="jit(step)/shard_map/amp.update/cond" stack_frame_id=8}
}
'''


# excerpts of the same step's text as PR 38's tree compiles it (cut from the
# step compiled for a described v5e, which is the text the chip compiles;
# names, operands and metadata as printed, shapes' tilings and the configs
# cut): the fp32 gradient pack as the compiler splits it (a chain of
# in-place updates over one buffer, of which only the links that fuse the
# pack's ``convert`` keep an ``op_name``, and a ``concatenate`` joined to it
# under ``amp.unscale``), a weight gradient's relayout on its way into the
# pack, and a weight staged into the fast memory space for the backward pass
V5E_DATAFLOW = """
HloModule jit_step, is_scheduled=true

%fused_computation.1442 (param_0.3253: f32[1024]) -> f32[265572352] {
  %custom-call.13 = f32[265572352]{0} custom-call(), custom_call_target="AllocateBuffer"
  %param_0.3253 = f32[1024]{0} parameter(0)
  %constant.1307 = s32[] constant(0)
  ROOT %dynamic-update-slice.511 = f32[265572352]{0} dynamic-update-slice(%custom-call.13, %param_0.3253, %constant.1307)
}

%fused_computation.1439 (param_0.3189: f32[265572352], param_1.2783: f32[1024]) -> f32[265572352] {
  %param_0.3189 = f32[265572352]{0} parameter(0)
  %param_1.2783 = f32[1024]{0} parameter(1)
  %constant.1303 = s32[] constant(1024)
  ROOT %dynamic-update-slice.510 = f32[265572352]{0} dynamic-update-slice(%param_0.3189, %param_1.2783, %constant.1303)
}

%fused_computation.1438 (param_0.3188: f32[265572352], param_1.2827: bf16[1024]) -> f32[265572352] {
  %param_0.3188 = f32[265572352]{0} parameter(0)
  %param_1.2827 = bf16[1024]{0} parameter(1)
  %convert_element_type.520 = f32[1024]{0} convert(%param_1.2827), metadata={op_name="jit(step)/amp.pack/convert_element_type" stack_frame_id=2}
  %constant.1302 = s32[] constant(2048)
  ROOT %dynamic-update-slice.509 = f32[265572352]{0} dynamic-update-slice(%param_0.3188, %convert_element_type.520, %constant.1302)
}

%fused_computation.1206 (param_0.2956: f32[265572352], param_1.2529: f32[1048576]) -> f32[265572352] {
  %param_0.2956 = f32[265572352]{0} parameter(0)
  %param_1.2529 = f32[1048576]{0:S(1)} parameter(1)
  %constant.1049 = s32[] constant(264523776)
  ROOT %dynamic-update-slice.277 = f32[265572352]{0} dynamic-update-slice(%param_0.2956, %param_1.2529, %constant.1049)
}

%fused_computation.1096 (param_0.2808: bf16[8,512,1024], param_1.2353: bf16[8,512,1024]) -> f32[128,8,8,128] {
  %param_0.2808 = bf16[8,512,1024]{2,1,0} parameter(0)
  %param_1.2353 = bf16[8,512,1024]{2,1,0} parameter(1)
  %convolution.763 = bf16[1024,1024,1]{1,0,2} convolution(%param_0.2808, %param_1.2353), window={size=8}, dim_labels=0fb_0io->bf0, metadata={op_name="jit(step)/transpose(jvp(model))/BertForPretraining/mlm_dense/dot_general" stack_frame_id=2}
  %bitcast.2539 = bf16[1024,1024]{1,0} bitcast(%convolution.763), metadata={op_name="jit(step)/transpose(jvp(model))/BertForPretraining/mlm_dense/dot_general" stack_frame_id=2}
  %convert.333 = f32[1024,1024]{1,0} convert(%bitcast.2539), metadata={op_name="jit(step)/amp.pack/convert_element_type" stack_frame_id=2}
  ROOT %bitcast.2513 = f32[128,8,8,128]{3,2,1,0} bitcast(%convert.333)
}

%fused_computation.9 (param_0.22: f32[70627328], param_1.2902: f32[265572352]) -> f32[2626560,128] {
  %param_1.2902 = f32[265572352]{0} parameter(1)
  %constant.1320 = f32[] constant(-inf)
  %pad.16 = f32[336199680]{0} pad(%param_1.2902, %constant.1320), padding=0_70627328, metadata={op_name="jit(step)/amp.pack/concatenate" stack_frame_id=2}
  %param_0.22 = f32[70627328]{0} parameter(0)
  %pad.15 = f32[336199680]{0} pad(%param_0.22, %constant.1320), padding=265572352_0, metadata={op_name="jit(step)/amp.pack/concatenate" stack_frame_id=2}
  %maximum.3 = f32[336199680]{0} maximum(%pad.16, %pad.15), metadata={op_name="jit(step)/amp.pack/concatenate" stack_frame_id=2}
  ROOT %bitcast.1883 = f32[2626560,128]{1,0} bitcast(%maximum.3), metadata={op_name="jit(step)/amp.unscale/jit(_scale_flat)/reshape" stack_frame_id=96}
}

%fused_computation.1093 (param_0.2790: bf16[4096,1024], param_1.2341: bf16[1024,1024]) -> bf16[4096,1024] {
  %param_0.2790 = bf16[4096,1024]{1,0} parameter(0)
  %param_1.2341 = bf16[1024,1024]{0,1:S(1)} parameter(1)
  ROOT %convolution.760 = bf16[4096,1024]{1,0} convolution(%param_0.2790, %param_1.2341), dim_labels=bf_io->bf, metadata={op_name="jit(step)/transpose(jvp(model))/BertForPretraining/bert/4/attention/out/dot_general" stack_frame_id=2}
}

ENTRY %main.253 (args_0: bf16[1024,1024], args_1: bf16[8,512,1024], args_2: s32[8]) -> (f32[2626560,128], bf16[4096,1024], s32[8], s32[8], s32[8]) {
  %args_0 = bf16[1024,1024]{1,0} parameter(0), metadata={op_name="args[0][0][\'bert\'][\'layer\'][\'4\'][\'attention\'][\'out\'][\'weight\']"}
  %args_1 = bf16[8,512,1024]{2,1,0} parameter(1)
  %args_2 = s32[8]{0} parameter(2)
  %slice-start.1088 = ((bf16[1024,1024]{1,0}), bf16[512,1024]{1,0:S(1)}, s32[]{:S(2)}) slice-start(%args_0), slice={[0:512], [0:1024]}
  %slice-start.1089 = ((bf16[1024,1024]{1,0}), bf16[512,1024]{1,0:S(1)}, s32[]{:S(2)}) slice-start(%args_0), slice={[512:1024], [0:1024]}
  %slice-done.1088 = bf16[512,1024]{1,0:S(1)} slice-done(%slice-start.1088)
  %slice-done.1089 = bf16[512,1024]{1,0:S(1)} slice-done(%slice-start.1089)
  %custom-call.286 = bf16[1024,1024]{1,0:S(1)} custom-call(%slice-done.1088, %slice-done.1089), custom_call_target="ConcatBitcast"
  %copy.829 = bf16[1024,1024]{0,1} copy(%custom-call.286), metadata={op_name="args[0][0][\'bert\'][\'layer\'][\'4\'][\'attention\'][\'out\'][\'weight\']"}
  %copy-start.152 = (bf16[1024,1024]{0,1:S(1)}, bf16[1024,1024]{0,1}, u32[]{:S(2)}) copy-start(%copy.829)
  %layer_norm_bwd.99 = (bf16[4096,1024]{1,0:S(1)}, f32[1,1024]{1,0}, f32[1,1024]{1,0}) custom-call(%args_1), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(model))/BertForPretraining/bert/embeddings_ln/jit(_bwd)/layer_norm_bwd/pallas_call" stack_frame_id=78}
  %pallas_call.593 = bf16[4096,1024]{1,0:S(1)} get-tuple-element(%layer_norm_bwd.99), index=0, metadata={op_name="jit(step)/transpose(jvp(model))/BertForPretraining/bert/embeddings_ln/jit(_bwd)/layer_norm_bwd/pallas_call" stack_frame_id=78}
  %pallas_call.595 = f32[1,1024]{1,0} get-tuple-element(%layer_norm_bwd.99), index=2, metadata={op_name="jit(step)/transpose(jvp(model))/BertForPretraining/bert/embeddings_ln/jit(_bwd)/layer_norm_bwd/pallas_call" stack_frame_id=78}
  %bitcast.3922 = f32[1024]{0} bitcast(%pallas_call.595)
  %pallas_call.594 = f32[1,1024]{1,0} get-tuple-element(%layer_norm_bwd.99), index=1, metadata={op_name="jit(step)/transpose(jvp(model))/BertForPretraining/bert/embeddings_ln/jit(_bwd)/layer_norm_bwd/pallas_call" stack_frame_id=78}
  %bitcast.3921 = f32[1024]{0} bitcast(%pallas_call.594)
  %constant_dynamic-update-slice_fusion.234 = f32[265572352]{0} fusion(%bitcast.3922), kind=kLoop, calls=%fused_computation.1442
  %constant_dynamic-update-slice_fusion.233 = f32[265572352]{0} fusion(%constant_dynamic-update-slice_fusion.234, %bitcast.3921), kind=kLoop, calls=%fused_computation.1439
  %copy-done.152 = bf16[1024,1024]{0,1:S(1)} copy-done(%copy-start.152)
  %fusion.1093 = bf16[4096,1024]{1,0} fusion(%pallas_call.593, %copy-done.152), kind=kOutput, calls=%fused_computation.1093, metadata={op_name="jit(step)/transpose(jvp(model))/BertForPretraining/bert/4/attention/out/dot_general" stack_frame_id=2}
  %reduce_sum.1667 = bf16[1024]{0} reduce(%fusion.1093, %args_2), dimensions={0}, to_apply=%region_212.232, metadata={op_name="jit(step)/transpose(jvp(model))/BertForPretraining/bert/0/attention/out/reduce_sum" stack_frame_id=2}
  %constant_dynamic-update-slice_fusion.232 = f32[265572352]{0} fusion(%constant_dynamic-update-slice_fusion.233, %reduce_sum.1667), kind=kLoop, calls=%fused_computation.1438
  %convert_bitcast_fusion.72 = f32[128,8,8,128]{3,2,1,0:S(1)} fusion(%args_1, %args_1), kind=kOutput, calls=%fused_computation.1096, metadata={op_name="jit(step)/transpose(jvp(model))/BertForPretraining/mlm_dense/dot_general" stack_frame_id=2}
  %copy.1216 = f32[128,8,8,128]{3,1,2,0} copy(%convert_bitcast_fusion.72)
  %bitcast.3612 = f32[1048576]{0} bitcast(%copy.1216)
  %constant_dynamic-update-slice_fusion = f32[265572352]{0} fusion(%constant_dynamic-update-slice_fusion.232, %bitcast.3612), kind=kLoop, calls=%fused_computation.1206
  %get-tuple-element.660 = f32[3,1024]{1,0} get-tuple-element(%layer_norm_bwd.99), index=1
  %reshape.1939 = f32[3072]{0} reshape(%get-tuple-element.660), metadata={op_name="jit(step)/amp.pack/convert_element_type" stack_frame_id=2}
  %copy.1203 = f32[128,8,8,128]{3,1,2,0} copy(%convert_bitcast_fusion.72)
  %bitcast.3251 = f32[1048576]{0} bitcast(%copy.1203)
  %concatenate.64 = f32[70627328]{0} concatenate(%reshape.1939, %bitcast.3251)
  %maximum_bitcast_fusion.1 = f32[2626560,128]{1,0} fusion(%concatenate.64, %constant_dynamic-update-slice_fusion), kind=kLoop, calls=%fused_computation.9, metadata={op_name="jit(step)/amp.unscale/jit(_scale_flat)/reshape" stack_frame_id=96}
  %copy.2711 = s32[8]{0:S(1)} copy(%args_2)
  %iota.5 = s32[8]{0} iota(), iota_dimension=0
  %copy-start.254 = (s32[8]{0}, s32[8]{0:S(1)}, u32[]{:S(2)}) copy-start(%copy.2711)
  %copy-done.254 = s32[8]{0} copy-done(%copy-start.254)
  ROOT %tuple.99 = (f32[2626560,128]{1,0}, bf16[4096,1024]{1,0}, s32[8]{0}, s32[8]{0}, s32[8]{0}) tuple(%maximum_bitcast_fusion.1, %fusion.1093, %copy-done.254, %iota.5, %copy.2711)
}
"""

TEXTS = {"step": V5E_TEXT, "dataflow": V5E_DATAFLOW}
PACK, ATTN_OUT_BWD = (("amp.pack",), False), (("model", "BertForPretraining/bert/4/attention/out"),
                                              True)

# (excerpt, instruction, its phase, where the phase came from)
ON_V5E_TEXT = [
    ("step", "_adam_flat.1", (("amp.update", "optim.adam"), False), "own"),
    ("step", "flash_fwd.3", (("model", "BertForPretraining/bert/3/attention"), False), "own"),
    ("step", "fusion.431", (("model", "BertForPretraining/bert/3/intermediate"), True), "own"),
    ("step", "slice.40", (("amp.update", "amp.rebuild"), False), "own"),
    # no op_name, inside the branch: the conditional's phase, whoever reads it
    ("step", "copy-start.7", (("amp.update",), False), "container"),
    ("step", "copy-done.7", (("amp.update",), False), "container"),
    # no op_name, a fusion: what it fused
    ("step", "convert_bitcast_fusion.98", (("amp.pack",), False), "fused"),
    # no op_name anywhere near, and nothing with a phase along the dataflow: unscoped
    ("step", "constant_dynamic-update-slice_fusion.234", ((), False), "none"),
    ("step", "copy.2711", ((), False), "none"),
    ("step", "conditional.1", (("amp.update",), False), "own"),
    # the pack's chain: a link that fused the pack's convert keeps it ..
    ("dataflow", "constant_dynamic-update-slice_fusion.232", PACK, "fused"),
    # .. the first link (its buffer is allocated inside it, its update is a
    # gradient of ``model``) and the next take it from the links after them ..
    ("dataflow", "constant_dynamic-update-slice_fusion.234", PACK, "sibling"),
    ("dataflow", "constant_dynamic-update-slice_fusion.233", PACK, "sibling"),
    # .. and the last one, which ``amp.unscale`` reads, from the link before it
    ("dataflow", "constant_dynamic-update-slice_fusion", PACK, "sibling"),
    ("dataflow", "dynamic-update-slice.277", PACK, "container"),
    # the second piece: no chain, a first operand under amp.pack, the reader amp.unscale's
    ("dataflow", "concatenate.64", PACK, "sibling"),
    ("dataflow", "maximum_bitcast_fusion.1", (("amp.unscale",), False), "own"),
    # a weight gradient's relayout is made for the pack that reads it, not for
    # the backward matmul that wrote its operand
    ("dataflow", "convert_bitcast_fusion.72", (("model", "BertForPretraining/mlm_dense"), True),
     "own"),
    ("dataflow", "copy.1216", PACK, "reader"),
    ("dataflow", "copy.1203", PACK, "reader"),
    # a weight staged for the backward matmul: the slices' and the copy's time
    # is on their ``-done``, and the relayout between them (its op_name is the
    # argument's: no scope) is read through the join of the slices ..
    ("dataflow", "slice-done.1088", ATTN_OUT_BWD, "reader"),
    ("dataflow", "slice-done.1089", ATTN_OUT_BWD, "reader"),
    ("dataflow", "copy.829", ATTN_OUT_BWD, "reader"),
    ("dataflow", "copy-done.152", ATTN_OUT_BWD, "reader"),
    # .. while a ``-start`` (the ``Async XLA Ops`` line names a start-to-done
    # span by it, and ``ddp.step_ms`` adds such spans whole) and the compiler's
    # own ``custom-call`` (``*.grouped_dot_roofline`` counts every custom-call
    # under ``moe.experts`` as a product's kernel) stay what they were
    ("dataflow", "slice-start.1088", ((), False), "none"),
    ("dataflow", "copy-start.152", ((), False), "none"),
    ("dataflow", "custom-call.286", ((), False), "none"),
    ("dataflow", "fusion.1093", ATTN_OUT_BWD, "own"),
    # every operand and every reader without a phase: no rule explains them
    ("dataflow", "copy.2711", ((), False), "none"),
    ("dataflow", "copy-start.254", ((), False), "none"),
    ("dataflow", "copy-done.254", ((), False), "none"),
    # not a move and not a join: no rule is tried
    ("dataflow", "iota.5", ((), False), "none"),
]


@pytest.mark.parametrize("text,name,expected,source", ON_V5E_TEXT)
def test_instruction_phases_on_v5e_text(text, name, expected, source):
    assert phases.instruction_phases(TEXTS[text])[name] == expected


@pytest.mark.parametrize("text,name,expected,source", ON_V5E_TEXT)
def test_instruction_phase_sources_on_v5e_text(text, name, expected, source):
    assert phases.instruction_phase_sources(TEXTS[text])[name] == source
    assert source in phases.SOURCES


def test_sources_and_phases_cover_the_same_instructions_and_agree():
    for text in TEXTS.values():
        got, sources = phases.instruction_phases(text), phases.instruction_phase_sources(text)
        assert set(got) == set(sources)
        assert all((sources[n] == "none") == (not got[n][0]) for n in got)


@pytest.mark.parametrize("text,fusion,held", [
    # a weight-gradient matmul of the backward pass with the pack's fp32 convert on its output
    ("dataflow", "convert_bitcast_fusion.72", {"model.bwd": 2, "amp.pack": 1}),
    # the join of the pack's two pieces, filed under its root's amp.unscale
    ("dataflow", "maximum_bitcast_fusion.1", {"amp.pack": 3, "amp.unscale": 1}),
    ("dataflow", "constant_dynamic-update-slice_fusion.232", None),     # one phase: not mixed
    ("dataflow", "fusion.1093", None),
    ("step", "fusion.431", None),
])
def test_fusion_phase_mix_on_v5e_text(text, fusion, held):
    assert phases.fusion_phase_mix(TEXTS[text]).get(fusion) == held


# what PR 37's ``instruction_phases`` gave the instructions of V5E_DATAFLOW
# that had a phase then (recorded from that function): none of them may move
PHASES_BEFORE_THE_DATAFLOW_RULES = {
    "convert_element_type.520": PACK, "constant_dynamic-update-slice_fusion.232": PACK,
    "convert.333": PACK, "pad.16": PACK, "pad.15": PACK, "maximum.3": PACK,
    "reshape.1939": PACK,
    **dict.fromkeys(("maximum_bitcast_fusion.1", "bitcast.1883", "param_1.2902", "param_0.22",
                     "constant.1320"), (("amp.unscale",), False)),
    **dict.fromkeys(("convert_bitcast_fusion.72", "convolution.763", "bitcast.2539",
                     "bitcast.2513", "param_0.2808", "param_1.2353"),
                    (("model", "BertForPretraining/mlm_dense"), True)),
    **dict.fromkeys(("fusion.1093", "convolution.760", "param_0.2790", "param_1.2341"),
                    ATTN_OUT_BWD),
    **dict.fromkeys(("layer_norm_bwd.99", "pallas_call.593", "pallas_call.594",
                     "pallas_call.595"),
                    (("model", "BertForPretraining/bert/embeddings_ln"), True)),
    "reduce_sum.1667": (("model", "BertForPretraining/bert/0/attention/out"), True),
}


def test_a_phase_read_from_metadata_is_never_changed_by_the_dataflow():
    got = phases.instruction_phases(V5E_DATAFLOW)
    sources = phases.instruction_phase_sources(V5E_DATAFLOW)
    for name, before in PHASES_BEFORE_THE_DATAFLOW_RULES.items():
        assert got[name] == before, name
        assert sources[name] in ("own", "fused", "container"), name
    moved = {n for n in got if got[n][0] and n not in PHASES_BEFORE_THE_DATAFLOW_RULES}
    assert moved and all(sources[n] in ("sibling", "reader", "operand", "container")
                         for n in moved)


def test_the_same_text_gives_the_same_answer():
    first = (phases.instruction_phases(V5E_DATAFLOW), phases.instruction_phase_sources(
        V5E_DATAFLOW), phases.fusion_phase_mix(V5E_DATAFLOW))
    phases.instruction_phases(V5E_TEXT)                 # another text in between
    again = (phases.instruction_phases(V5E_DATAFLOW + ""), phases.instruction_phase_sources(
        V5E_DATAFLOW), phases.fusion_phase_mix(V5E_DATAFLOW))
    assert first == again
    assert list(first[0]) == list(again[0])             # and in the text's order
    first[0].clear()                                    # a caller's dict is its own
    assert phases.instruction_phases(V5E_DATAFLOW) == again[0]


def test_the_walks_end_where_the_dataflow_gives_out():
    """Moves that read each other (no such text comes from a compiler), a
    join whose chain and operands hold no phase, a move between two of them:
    every walk ends and the answer is ``unscoped``."""
    text = """HloModule m
ENTRY %main (p: f32[8]) -> f32[16] {
  %p = f32[8]{0} parameter(0)
  %copy.1 = f32[8]{0} copy(%copy.2)
  %copy.2 = f32[8]{0} copy(%copy.1)
  %copy-start.3 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%copy.2)
  %copy-done.3 = f32[8]{0} copy-done(%copy-start.3)
  %dynamic-update-slice.4 = f32[16]{0} dynamic-update-slice(%dynamic-update-slice.5, %copy-done.3, %p)
  %dynamic-update-slice.5 = f32[16]{0} dynamic-update-slice(%dynamic-update-slice.4, %copy.1, %p)
  ROOT %concatenate.6 = f32[16]{0} concatenate(%copy.2, %copy-done.3)
}
"""
    assert set(phases.instruction_phases(text).values()) == {((), False)}
    assert set(phases.instruction_phase_sources(text).values()) == {"none"}
    assert phases.fusion_phase_mix(text) == {}


@pytest.mark.parametrize("kernel", ["_adam_flat", "_scale_flat", "layer_norm_fwd",
                                    "layer_norm_bwd", "flash_fwd", "flash_dq", "flash_dkv"])
def test_kernel_names_are_what_the_readers_match_on(kernel, monkeypatch):
    """A scope changes ``op_name``; the Mosaic call keeps the kernel's own
    name, which becomes the HLO instruction's (``%_adam_flat.1``)."""
    monkeypatch.setenv("APEX_TPU_FORCE_PALLAS", "1")
    from apex_tpu.multi_tensor_apply import multi_tensor_scale
    from apex_tpu.normalization import FusedLayerNorm
    from apex_tpu.transformer import attention

    def program(x, q):
        ln = FusedLayerNorm(128)
        ln_params = ln.init(jax.random.PRNGKey(0))[0]

        def loss(x, q):
            with jax.named_scope("model"):
                y = ln(ln_params, x)
                a = attention.dot_product_attention(q, q, q)
            return jnp.sum(y) + jnp.sum(a.astype(jnp.float32))
        grads = jax.grad(loss, argnums=(0, 1))(x, q)
        opt, flat = optimizers.FusedAdam(1e-3), x.reshape(-1)
        with jax.named_scope("amp.unscale"):
            scaled, _ = multi_tensor_scale(flat, 0.5)
        return opt.step(flat, opt.init(flat), scaled), grads

    jaxpr = jax.make_jaxpr(program)(jnp.ones((8, 128)), jnp.ones((1, 2, 128, 64), jnp.bfloat16))
    assert kernel in _call_names(jaxpr.jaxpr)


def _call_names(jaxpr, out=None):
    """Names of the Pallas calls and of the jitted functions around them: a
    Mosaic call without a name of its own takes its jit's (``_adam_flat``)."""
    out = set() if out is None else out
    for eqn in jaxpr.eqns:
        if "name" in eqn.params:         # pallas_call and jit alike
            out.add(eqn.params["name"])
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple)) else [value]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _call_names(inner, out)
    return out


# -- the ledger ------------------------------------------------------------------

def test_compiled_text_after_one_donated_step(programs):
    led = programs["ledger"]
    assert led["text"].startswith("HloModule") and "amp.update" in led["text"]
    assert led["again"] is led["text"]                 # computed once, then kept


def test_a_steady_dispatch_stores_nothing(programs):
    led = programs["ledger"]
    assert led["first"] == led["second"]


def test_reading_the_compiled_text_is_not_a_trace(programs):
    led = programs["ledger"]
    assert led["after_text"] == led["first"] and led["traces"] >= 1
    assert json.loads(led["first"])["traces"] == 1


def test_compiled_text_of_an_unknown_or_dead_entry_is_none():
    led = C.CompilationLedger()
    assert led.compiled_text("never.traced") is None
    f = C.instrumented_jit(lambda x: x + 1, "t.dead", ledger=led)
    f(jnp.ones(3))
    del f
    import gc
    gc.collect()
    assert led.compiled_text("t.dead") is None


# -- set-up spans -------------------------------------------------------------------

@pytest.mark.parametrize("span", BUILD_SPANS)
def test_build_leaves_its_span(programs, span):
    _, events = programs["spans"]
    assert [e["name"] for e in events if e["name"] == span] == [span]


def test_build_spans_follow_one_another_on_a_readable_origin(programs):
    origin, events = programs["spans"]
    spans = [e for e in events if e["name"] in BUILD_SPANS]
    assert [e["name"] for e in spans] == list(BUILD_SPANS)
    for a, b in zip(spans, spans[1:]):
        assert a["ts"] + a["dur"] <= b["ts"]
    import time
    now = time.perf_counter()
    assert all(origin <= origin + e["ts"] / 1e6 <= now for e in spans)


def test_the_package_records_its_own_import():
    (ev,) = [e for e in get_recorder().events() if e["name"] == "apex_tpu.import"]
    assert ev["dur"] > 0 and ev["ts"] < 0        # began before the recorder existed
    rec = SpanRecorder()
    rec.add_span("x", rec.origin - 2.0, rec.origin - 0.5)
    (ev,) = rec.events()
    assert ev["ts"] == pytest.approx(-2e6) and ev["dur"] == pytest.approx(1.5e6)


def test_a_rematerialized_blocks_backward_keeps_its_module_path(programs):
    """``transpose(jvp(model))/jvp(model)/checkpoint/layers/1/mlp/moe.experts/..``:
    the repeated root scope and jax's own ``checkpoint`` / ``rematted_computation``
    are stepped over, so the backward of a rematerialized block is read by module
    like its forward."""
    found = set(programs["moe"][1].values())
    for backward in (False, True):
        assert (("model", "layers/1/mlp", "moe.experts"), backward) in found
        assert any(path[:2] == ("model", "layers/1/self_attn/q_proj") and b == backward
                   for path, b in found)
    assert not any(path[:2] == ("model", "model") for path, _ in found)
    name = ("jit(f)/transpose(jvp(model))/jvp(model)/checkpoint/rematted_computation/layers/0/"
            "self_attn/mul;jit(f)/jvp(loss)/add")
    assert phases.phase_of_op_name(name) == (("model", "layers/0/self_attn"), True)


def test_a_custom_call_the_compiler_named_takes_its_operands_or_its_readers_phase():
    """``lax.ragged_dot`` becomes the TPU compiler's own kernel with
    ``op_name="ragged-dot-none"``: the scope it was written under is on its
    operand's producer, or (a weight gradient read from a prefetch) on its reader."""
    text = """HloModule m
ENTRY %main (p: f32[8,8]) -> f32[8,8] {
  %p = f32[8,8]{1,0} parameter(0)
  %fusion.1 = f32[8,8]{1,0} fusion(%p), kind=kLoop, calls=%f1, metadata={op_name="jit(s)/jvp(model)/layers/1/mlp/moe.experts/select_n"}
  %ragged-dot-none = f32[8,8]{1,0} custom-call(%p, %fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %ragged-dot-none.1 = f32[8,8]{1,0} custom-call(%p, %p), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %fusion.2 = f32[8,8]{1,0} fusion(%ragged-dot-none.1), kind=kLoop, calls=%f2, metadata={op_name="jit(s)/transpose(jvp(model))/layers/1/mlp/moe.experts/mul"}
  %custom-call.9 = f32[8,8]{1,0} custom-call(%p), custom_call_target="Other", metadata={op_name="Other"}
  ROOT %flash_fwd.3 = f32[8,8]{1,0} custom-call(%fusion.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(s)/jvp(model)/layers/1/self_attn/pallas_call"}
}
"""
    got = phases.instruction_phases(text)
    assert got["ragged-dot-none"] == (("model", "layers/1/mlp", "moe.experts"), False)
    assert got["ragged-dot-none.1"] == (("model", "layers/1/mlp", "moe.experts"), True)
    assert got["custom-call.9"] == ((), False)
    assert got["flash_fwd.3"] == (("model", "layers/1/self_attn"), False)
    # through the one walk of PR 38: operands first for a call the compiler named
    sources = phases.instruction_phase_sources(text)
    assert sources["ragged-dot-none"] == "operand" and sources["ragged-dot-none.1"] == "reader"
    assert sources["custom-call.9"] == "none" and sources["flash_fwd.3"] == "own"
