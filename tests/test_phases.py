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
            ("conv", "attn.qk_norm")])


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


@pytest.mark.parametrize("name,expected", [
    ("_adam_flat.1", (("amp.update", "optim.adam"), False)),
    ("flash_fwd.3", (("model", "BertForPretraining/bert/3/attention"), False)),
    ("fusion.431", (("model", "BertForPretraining/bert/3/intermediate"), True)),
    ("slice.40", (("amp.update", "amp.rebuild"), False)),
    # no op_name, inside the branch: the conditional's phase
    ("copy-start.7", (("amp.update",), False)),
    ("copy-done.7", (("amp.update",), False)),
    # no op_name, a fusion: what it fused
    ("convert_bitcast_fusion.98", (("amp.pack",), False)),
    # no op_name anywhere near: unscoped
    ("constant_dynamic-update-slice_fusion.234", ((), False)),
    ("copy.2711", ((), False)),
    ("conditional.1", (("amp.update",), False)),
])
def test_instruction_phases_on_v5e_text(name, expected):
    assert phases.instruction_phases(V5E_TEXT)[name] == expected


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
