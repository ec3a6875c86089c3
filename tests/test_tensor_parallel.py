"""Tensor-parallel layer parity: sharded column/row linears, the MLP
block, and head-sharded attention must match their dense single-device
equivalents bitwise-closely — outputs AND gradients — on the virtual
mesh, with params entering shard_map through partition_specs.

(Beyond the reference: SURVEY.md §2.3 lists its parallelism inventory as
data-parallel only.  These are the Megatron patterns expressed as mesh
collectives.)
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu import nn
from apex_tpu.nn import functional as F
from apex_tpu.parallel import tensor_parallel as tp
from apex_tpu.parallel import DistributedDataParallel


def tp_mesh(tp_size=4):
    return Mesh(np.array(jax.devices()[:tp_size]), ("model",))


def _run_sharded(mesh, fn, params, specs, *args, arg_specs=None,
                 out_specs=P()):
    arg_specs = arg_specs or tuple(P() for _ in args)
    return jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=(specs, *arg_specs), out_specs=out_specs,
        check_vma=False))(params, *args)


def test_column_row_mlp_matches_dense():
    mesh = tp_mesh(4)
    mlp = tp.ParallelMLP(16, 64)
    params, _ = mlp.init(jax.random.PRNGKey(0))
    specs = tp.partition_specs(mlp, params)
    # specs mark the TP dims
    assert specs["fc_in"]["weight"] == P("model", None)
    assert specs["fc_in"]["bias"] == P("model")
    assert specs["fc_out"]["weight"] == P(None, "model")
    assert specs["fc_out"]["bias"] == P()

    x = jnp.asarray(np.random.RandomState(0).randn(4, 6, 16), jnp.float32)

    def fwd(p, xb):
        return mlp(p, xb)

    y_tp = _run_sharded(mesh, fwd, params, specs, x)
    # dense reference: same math on the full params outside any mesh
    y_ref = mlp(params, x)
    np.testing.assert_allclose(np.asarray(y_tp), np.asarray(y_ref),
                               atol=2e-5)


def test_mlp_gradients_match_dense():
    mesh = tp_mesh(4)
    mlp = tp.ParallelMLP(8, 32, activation="relu")
    params, _ = mlp.init(jax.random.PRNGKey(1))
    specs = tp.partition_specs(mlp, params)
    x = jnp.asarray(np.random.RandomState(1).randn(2, 5, 8), jnp.float32)

    def loss(p, xb):
        return jnp.sum(jnp.square(mlp(p, xb)))

    g_tp = jax.jit(jax.shard_map(
        jax.grad(loss), mesh=mesh, in_specs=(specs, P()),
        out_specs=specs, check_vma=False))(params, x)
    g_ref = jax.grad(loss)(params, x)
    _assert_trees_close(g_tp, g_ref, atol=2e-4)


from conftest import assert_trees_close as _assert_trees_close  # noqa: E402


def test_column_gather_output():
    mesh = tp_mesh(4)
    col = tp.ColumnParallelLinear(8, 16, gather_output=True)
    params, _ = col.init(jax.random.PRNGKey(2))
    specs = tp.partition_specs(col, params)
    x = jnp.asarray(np.random.RandomState(2).randn(3, 8), jnp.float32)
    y = _run_sharded(mesh, lambda p, xb: col(p, xb), params, specs, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(col(params, x)),
                               atol=2e-5)
    assert y.shape == (3, 16)

    # gradient path: the all_gather must transpose to SPLIT, not
    # reduce-scatter of the replicated cotangent (axis_size inflation)
    def loss(p, xb):
        return jnp.sum(jnp.square(col(p, xb)))

    g_tp = jax.jit(jax.shard_map(
        jax.grad(loss), mesh=mesh, in_specs=(specs, P()),
        out_specs=specs, check_vma=False))(params, x)
    _assert_trees_close(g_tp, jax.grad(loss)(params, x), atol=2e-4)


def test_row_scatter_input():
    """input_is_parallel=False: a replicated input is sliced down to the
    device's feature block before the local contraction."""
    mesh = tp_mesh(4)
    row = tp.RowParallelLinear(16, 8, input_is_parallel=False)
    params, _ = row.init(jax.random.PRNGKey(3))
    specs = tp.partition_specs(row, params)
    x = jnp.asarray(np.random.RandomState(3).randn(3, 16), jnp.float32)
    y = _run_sharded(mesh, lambda p, xb: row(p, xb), params, specs, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(row(params, x)),
                               atol=2e-5)


@pytest.mark.slow
@pytest.mark.parametrize("causal", [False, True])
def test_parallel_attention_matches_dense(causal):
    mesh = tp_mesh(4)
    attn = tp.ParallelSelfAttention(32, 8, causal=causal)
    params, _ = attn.init(jax.random.PRNGKey(4))
    specs = tp.partition_specs(attn, params)
    x = jnp.asarray(np.random.RandomState(4).randn(2, 10, 32) * 0.3,
                    jnp.float32)

    def fwd(p, xb):
        out, _ = nn.apply(attn, p, xb, train=False)
        return out

    y_tp = _run_sharded(mesh, fwd, params, specs, x)
    y_ref = fwd(params, x)
    np.testing.assert_allclose(np.asarray(y_tp), np.asarray(y_ref),
                               atol=3e-5)

    # head-sharded attention grads: one f at block entry covers q/k/v
    def loss(p, xb):
        return jnp.sum(jnp.square(fwd(p, xb)))

    g_tp = jax.jit(jax.shard_map(
        jax.grad(loss), mesh=mesh, in_specs=(specs, P()),
        out_specs=specs, check_vma=False))(params, x)
    _assert_trees_close(g_tp, jax.grad(loss)(params, x), atol=5e-4)


def test_attention_head_divisibility_check():
    mesh = tp_mesh(4)
    attn = tp.ParallelSelfAttention(12, 6)   # 6 heads, tp=4: invalid
    params, _ = attn.init(jax.random.PRNGKey(5))
    specs = tp.partition_specs(attn, params)
    x = jnp.zeros((1, 4, 12))
    with pytest.raises(ValueError, match="not divisible"):
        _run_sharded(mesh, lambda p, xb: nn.apply(attn, p, xb)[0],
                     params, specs, x)


def test_dp_tp_combined_train_step():
    """2x4 (data, model) mesh: batch over data, TP params over model,
    DDP allreduce over data only — one step must match the single-device
    full-batch dense step."""
    devs = np.array(jax.devices()[:8]).reshape(2, 4)
    mesh = Mesh(devs, ("data", "model"))
    mlp = tp.ParallelMLP(8, 32, activation="relu")
    params, _ = mlp.init(jax.random.PRNGKey(6))
    specs = tp.partition_specs(mlp, params)
    ddp = DistributedDataParallel(mlp)
    rng = np.random.RandomState(6)
    x = jnp.asarray(rng.randn(8, 8), jnp.float32)
    y = jnp.asarray(rng.randn(8, 8), jnp.float32)
    lr = 0.1

    def step(p, xb, yb):
        def loss_fn(pp):
            return F.mse_loss(mlp(pp, xb), yb)
        grads = jax.grad(loss_fn)(p)
        grads = ddp.allreduce_grads(grads)     # data axis only
        return jax.tree_util.tree_map(lambda w, g: w - lr * g, p, grads)

    new_tp = jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=(specs, P("data"), P("data")),
        out_specs=specs, check_vma=False))(params, x, y)

    def ref_step(p):
        grads = jax.grad(lambda pp: F.mse_loss(mlp(pp, x), y))(p)
        return jax.tree_util.tree_map(lambda w, g: w - lr * g, p, grads)

    new_ref = ref_step(params)
    _assert_trees_close(new_tp, new_ref, atol=2e-5)


def test_parallel_attention_per_head_mask():
    """A (B, num_heads, Tq, Tk) mask is sliced to the device's head
    block, matching the dense full-head computation."""
    mesh = tp_mesh(4)
    attn = tp.ParallelSelfAttention(32, 8)
    params, _ = attn.init(jax.random.PRNGKey(7))
    specs = tp.partition_specs(attn, params)
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(2, 6, 32) * 0.3, jnp.float32)
    mask = jnp.asarray(rng.rand(2, 8, 6, 6) > 0.3)

    def fwd(p, xb, mb):
        out, _ = nn.apply(attn, p, xb, mask=mb, train=False)
        return out

    y_tp = jax.jit(jax.shard_map(
        fwd, mesh=mesh, in_specs=(specs, P(), P()), out_specs=P(),
        check_vma=False))(params, x, mask)
    np.testing.assert_allclose(np.asarray(y_tp),
                               np.asarray(fwd(params, x, mask)),
                               atol=3e-5)


def test_parallel_attention_train_dropout_decorrelated():
    """Train-mode output dropout folds the model-axis index into the rng
    so shards don't reuse one mask; smoke: runs, differs from eval."""
    mesh = tp_mesh(4)
    attn = tp.ParallelSelfAttention(32, 8, dropout=0.5)
    params, _ = attn.init(jax.random.PRNGKey(8))
    specs = tp.partition_specs(attn, params)
    x = jnp.asarray(np.random.RandomState(8).randn(2, 6, 32) * 0.3,
                    jnp.float32)

    def fwd(p, xb, train):
        out, _ = nn.apply(attn, p, xb, train=train,
                          rng=jax.random.PRNGKey(0))
        return out

    y_train = jax.jit(jax.shard_map(
        lambda p, xb: fwd(p, xb, True), mesh=mesh,
        in_specs=(specs, P()), out_specs=P(), check_vma=False))(params, x)
    y_eval = jax.jit(jax.shard_map(
        lambda p, xb: fwd(p, xb, False), mesh=mesh,
        in_specs=(specs, P()), out_specs=P(), check_vma=False))(params, x)
    assert np.isfinite(np.asarray(y_train)).all()
    assert np.abs(np.asarray(y_train) - np.asarray(y_eval)).max() > 1e-4


def test_vocab_parallel_embedding_matches_dense():
    mesh = tp_mesh(4)
    emb = tp.VocabParallelEmbedding(32, 16)
    params, _ = emb.init(jax.random.PRNGKey(9))
    specs = tp.partition_specs(emb, params)
    assert specs["weight"] == P("model", None)
    ids = jnp.asarray(np.random.RandomState(9).randint(0, 32, (3, 7)))

    y_tp = _run_sharded(mesh, lambda p, i: emb(p, i), params, specs, ids)
    y_ref = emb(params, ids)          # unmapped: plain gather
    np.testing.assert_allclose(np.asarray(y_tp), np.asarray(y_ref),
                               atol=1e-6)

    # embedding-table grads: scatter-add lands on the owning shard only
    def loss(p, i):
        return jnp.sum(jnp.square(emb(p, i)))

    g_tp = jax.jit(jax.shard_map(
        jax.grad(loss), mesh=mesh, in_specs=(specs, P()),
        out_specs=specs, check_vma=False))(params, ids)
    _assert_trees_close(g_tp, jax.grad(loss)(params, ids), atol=1e-5)


@pytest.mark.slow
def test_vocab_parallel_cross_entropy_matches_dense():
    mesh = tp_mesh(4)
    rng = np.random.RandomState(10)
    V, B, T = 32, 2, 6
    logits = jnp.asarray(rng.randn(B, T, V) * 2, jnp.float32)
    labels = jnp.asarray(rng.randint(0, V, (B, T)))
    labels = labels.at[0, 0].set(-100)      # ignore_index token

    def tp_loss(lg, lb):
        return tp.vocab_parallel_cross_entropy(lg, lb)

    loss_tp = jax.jit(jax.shard_map(
        tp_loss, mesh=mesh, in_specs=(P(None, None, "model"), P()),
        out_specs=P(), check_vma=False))(logits, labels)

    # dense reference: masked mean NLL over the full vocab
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(
        logp, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    valid = labels != -100
    ref = jnp.sum(jnp.where(valid, nll, 0.0)) / jnp.sum(valid)
    np.testing.assert_allclose(float(loss_tp), float(ref), atol=1e-5)

    # logit grads: reassembled sharded grad == dense grad
    g_tp = jax.jit(jax.shard_map(
        jax.grad(tp_loss), mesh=mesh,
        in_specs=(P(None, None, "model"), P()),
        out_specs=P(None, None, "model"), check_vma=False))(logits, labels)
    g_ref = jax.grad(
        lambda lg: jnp.sum(jnp.where(
            valid,
            -jnp.take_along_axis(
                jax.nn.log_softmax(lg, -1),
                jnp.maximum(labels, 0)[..., None], -1)[..., 0],
            0.0)) / jnp.sum(valid))(logits)
    np.testing.assert_allclose(np.asarray(g_tp), np.asarray(g_ref),
                               atol=1e-5)


@pytest.mark.slow
def test_vocab_parallel_lm_pipeline_end_to_end():
    """Embedding -> MLP -> column LM head (parallel logits) -> vocab-
    parallel CE, grads flowing through every TP collective."""
    mesh = tp_mesh(4)

    class TinyLM(nn.Module):
        def __init__(self):
            super().__init__()
            self.emb = tp.VocabParallelEmbedding(32, 16)
            self.mlp = tp.ParallelMLP(16, 32)
            self.head = tp.ColumnParallelLinear(16, 32, bias=False)

        def forward(self, params, ids, labels):
            h = self.emb(params["emb"], ids)
            h = h + self.mlp(params["mlp"], h)
            logits = self.head(params["head"], h)   # vocab-sharded
            return tp.vocab_parallel_cross_entropy(logits, labels)

    lm = TinyLM()
    params, _ = lm.init(jax.random.PRNGKey(11))
    specs = tp.partition_specs(lm, params)
    rng = np.random.RandomState(11)
    ids = jnp.asarray(rng.randint(0, 32, (2, 5)))
    labels = jnp.asarray(rng.randint(0, 32, (2, 5)))

    def loss(p):
        return lm(p, ids, labels)

    l_tp = jax.jit(jax.shard_map(
        loss, mesh=mesh, in_specs=(specs,), out_specs=P(),
        check_vma=False))(params)
    l_ref = loss(params)              # unmapped degradation
    np.testing.assert_allclose(float(l_tp), float(l_ref), atol=1e-5)

    g_tp = jax.jit(jax.shard_map(
        jax.grad(loss), mesh=mesh, in_specs=(specs,), out_specs=specs,
        check_vma=False))(params)
    _assert_trees_close(g_tp, jax.grad(loss)(params), atol=2e-5)


# tier-1 budget (PR 2): slowest tests by --durations carry the slow
# marker so a cold `-m 'not slow'` run fits the 870 s timeout
@pytest.mark.slow
def test_bert_tensor_parallel_matches_unmapped():
    """models.BertForPretraining(tp_axis='model') on the mesh must match
    its own unmapped degradation (same params, same structure): loss and
    grads — the flagship-model integration of the TP stack."""
    from apex_tpu import models
    cfg = models.BertConfig(vocab_size=64, hidden_size=32,
                            num_hidden_layers=2, num_attention_heads=4,
                            intermediate_size=64,
                            max_position_embeddings=16,
                            hidden_dropout_prob=0.0,
                            attention_probs_dropout_prob=0.0,
                            tp_axis="model")
    model = models.BertForPretraining(cfg)
    params, _ = model.init(jax.random.PRNGKey(12))
    specs = tp.partition_specs(model, params)
    # the TP leaves actually got marked
    assert (specs["bert"]["word_embeddings"]["weight"]
            == P("model", None))
    l0 = specs["bert"]["layer"]["0"]
    assert l0["attention"]["core"]["q"]["weight"] == P("model", None)
    assert l0["mlp"]["fc_out"]["weight"] == P(None, "model")

    mesh = tp_mesh(4)
    rng = np.random.RandomState(12)
    ids = jnp.asarray(rng.randint(0, 64, (2, 8)))
    mlm = jnp.asarray(np.where(rng.rand(2, 8) < 0.3,
                               rng.randint(0, 64, (2, 8)), -100))
    nsp = jnp.asarray(rng.randint(0, 2, (2,)))

    def loss(p):
        return model.loss(p, ids, mlm, nsp)

    l_tp = jax.jit(jax.shard_map(
        loss, mesh=mesh, in_specs=(specs,), out_specs=P(),
        check_vma=False))(params)
    np.testing.assert_allclose(float(l_tp), float(loss(params)),
                               atol=1e-5)

    g_tp = jax.jit(jax.shard_map(
        jax.grad(loss), mesh=mesh, in_specs=(specs,), out_specs=specs,
        check_vma=False))(params)
    _assert_trees_close(g_tp, jax.grad(loss)(params), atol=5e-5)


@pytest.mark.slow
def test_amp_o2_fused_adam_with_tp_bert():
    """The apex core (amp O2 + FusedAdam flat masters + dynamic loss
    scale) composes with tensor parallelism: optimizer state is built
    from the LOCAL shards inside shard_map via sharded_optimizer_specs,
    and training descends on a (data, model) mesh with DDP on data."""
    from apex_tpu import amp, models, optimizers
    from jax import lax

    cfg = models.BertConfig(vocab_size=64, hidden_size=32,
                            num_hidden_layers=2, num_attention_heads=4,
                            intermediate_size=64,
                            max_position_embeddings=16,
                            hidden_dropout_prob=0.0,
                            attention_probs_dropout_prob=0.0,
                            tp_axis="model")
    model, optimizer = amp.initialize(models.BertForPretraining(cfg),
                                      optimizers.FusedAdam(lr=2e-3),
                                      opt_level="O2", verbosity=0)
    params, _ = model.init(jax.random.PRNGKey(0))
    specs = tp.partition_specs(model, params)
    devs = np.array(jax.devices()[:8]).reshape(2, 4)
    mesh = Mesh(devs, ("data", "model"))
    ospecs = tp.sharded_optimizer_specs(optimizer, params, specs, mesh)

    opt_state = jax.jit(jax.shard_map(
        optimizer.init, mesh=mesh, in_specs=(specs,), out_specs=ospecs,
        check_vma=False))(params)

    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, 64, (8, 8)))
    mlm = jnp.asarray(np.where(rng.rand(8, 8) < 0.3,
                               rng.randint(0, 64, (8, 8)), -100))
    nsp = jnp.asarray(rng.randint(0, 2, (8,)))

    def step(p, os, i, m, n):
        def loss_fn(pp):
            return model.loss(pp, i, m, n), ()
        loss, _, grads = amp.scaled_grad(loss_fn, p, os, has_aux=True)
        grads = jax.tree_util.tree_map(
            lambda g: lax.pmean(g, "data"), grads)
        # model-axis shards are disjoint: overflow decisions must merge
        p, os, info = optimizer.step(p, os, grads,
                                     found_inf_axes=("model",))
        return p, os, lax.pmean(loss, "data"), info["loss_scale"]

    train = jax.jit(jax.shard_map(
        step, mesh=mesh,
        in_specs=(specs, ospecs, P("data"), P("data"), P("data")),
        out_specs=(specs, ospecs, P(), P()), check_vma=False))

    l0 = None
    for _ in range(10):
        params, opt_state, loss, scale = train(params, opt_state, ids,
                                               mlm, nsp)
        if l0 is None:
            l0 = float(loss)
    assert float(loss) < l0, (l0, float(loss))
    assert float(scale) > 0


def test_tp_overflow_skip_is_global_across_shards():
    """An inf in ONE model-shard's grads must skip the step on EVERY
    shard (found_inf_axes pmax) — without the merge, the other shards
    would apply a partial update and the loss scales would diverge."""
    from apex_tpu import amp, optimizers
    from jax import lax

    mesh = tp_mesh(4)
    col = tp.ColumnParallelLinear(8, 16, bias=False)
    model, optimizer = amp.initialize(col, optimizers.FusedAdam(lr=0.1),
                                      opt_level="O2", verbosity=0,
                                      hard_override=True)
    params, _ = model.init(jax.random.PRNGKey(0))
    specs = tp.partition_specs(model, params)
    ospecs = tp.sharded_optimizer_specs(optimizer, params, specs, mesh)
    opt_state = jax.jit(jax.shard_map(
        optimizer.init, mesh=mesh, in_specs=(specs,), out_specs=ospecs,
        check_vma=False))(params)

    # grads: inf ONLY in rows 0..3 — device 0's weight block
    g = np.ones((16, 8), np.float32)
    g[1, 2] = np.inf
    grads = {"weight": jnp.asarray(g)}

    def step(p, os, gr, merge):
        kw = {"found_inf_axes": ("model",)} if merge else {}
        return optimizer.step(p, os, gr, **kw)

    for merge in (True, False):
        new_p, new_os, info = jax.jit(jax.shard_map(
            lambda p, os, gr, m=merge: step(p, os, gr, m), mesh=mesh,
            in_specs=(specs, ospecs, specs), out_specs=(specs, ospecs,
                                                        P()),
            check_vma=False))(params, opt_state, grads)
        w0 = np.asarray(params["weight"])
        w1 = np.asarray(new_p["weight"], np.float32)
        if merge:
            # everyone skipped: weights identical everywhere
            np.testing.assert_array_equal(np.asarray(w1), w0)
        else:
            # documents the hazard: only the inf-owning shard skipped,
            # the other three applied a partial update
            np.testing.assert_array_equal(w1[:4], w0[:4])
            assert np.abs(w1[4:] - w0[4:]).max() > 0


def test_tp_specs_shard_flat_buffers_whose_lengths_coincide():
    """amp stores its flat buffers at a block-aligned length, so a small
    model's LOCAL buffer (32 logical elements) and its GLOBAL one (128)
    both have 1024: the spec must come from the logical counts, or every
    shard would be handed shard 0's masters."""
    from apex_tpu import amp, optimizers

    mesh = tp_mesh(4)
    col = tp.ColumnParallelLinear(8, 16, bias=False)
    model, optimizer = amp.initialize(col, optimizers.FusedAdam(lr=0.1),
                                      opt_level="O2", verbosity=0,
                                      hard_override=True)
    params, _ = model.init(jax.random.PRNGKey(0))
    specs = tp.partition_specs(model, params)
    ospecs = tp.sharded_optimizer_specs(optimizer, params, specs, mesh)
    assert ospecs.masters.layout.total == 32
    assert ospecs.masters.layout.storage \
        == optimizer.init(params).masters.layout.storage == 1024
    assert ospecs.masters.buf == P("model")
    assert ospecs.inner.m == ospecs.inner.v == P("model")
    opt_state = jax.jit(jax.shard_map(
        optimizer.init, mesh=mesh, in_specs=(specs,), out_specs=ospecs,
        check_vma=False))(params)
    buf = np.asarray(opt_state.masters.buf).reshape(4, 1024)
    np.testing.assert_array_equal(
        buf[:, :32].reshape(16, 8),
        np.asarray(params["weight"], np.float32))
    assert not buf[:, 32:].any()


def test_checkpoint_roundtrip_with_tp_sharded_state(tmp_path):
    """Save/restore of TP-sharded train state (params + per-shard amp
    optimizer state): the gathered checkpoint restores to an identical
    trajectory — resume under TP (reference resume flow,
    examples/imagenet/main_amp.py:170-185, extended to sharded state)."""
    from apex_tpu import amp, optimizers
    from apex_tpu.utils import checkpoint as ckpt

    mesh = tp_mesh(4)
    mlp = tp.ParallelMLP(8, 32, activation="relu")
    model, optimizer = amp.initialize(mlp, optimizers.FusedAdam(lr=1e-2),
                                      opt_level="O2", verbosity=0,
                                      hard_override=True)
    params, _ = model.init(jax.random.PRNGKey(0))
    specs = tp.partition_specs(model, params)
    ospecs = tp.sharded_optimizer_specs(optimizer, params, specs, mesh)
    opt_state = jax.jit(jax.shard_map(
        optimizer.init, mesh=mesh, in_specs=(specs,), out_specs=ospecs,
        check_vma=False))(params)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(4, 6, 8), jnp.float32)
    y = jnp.asarray(rng.randn(4, 6, 8), jnp.float32)

    def step(p, os, xb, yb):
        def loss_fn(pp):
            out, _ = model.apply(pp, xb)
            return F.mse_loss(out, yb), ()
        loss, _, g = amp.scaled_grad(loss_fn, p, os, has_aux=True)
        p, os, _ = optimizer.step(p, os, g,
                                  found_inf_axes=("model",))
        return p, os, loss

    train = jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=(specs, ospecs, P(), P()),
        out_specs=(specs, ospecs, P()), check_vma=False))

    for _ in range(3):
        params, opt_state, _ = train(params, opt_state, x, y)

    # save (gathers shards to host), then CONTINUE two ways
    ckpt.save_checkpoint(str(tmp_path), 3, {"params": params,
                                            "opt": opt_state})
    restored = ckpt.restore_checkpoint(
        str(tmp_path), {"params": params, "opt": opt_state})
    p2, os2 = restored["params"], restored["opt"]

    traj_a, traj_b = [], []
    pa, osa, pb, osb = params, opt_state, p2, os2
    for _ in range(3):
        pa, osa, la = train(pa, osa, x, y)
        pb, osb, lb = train(pb, osb, x, y)
        traj_a.append(float(la))
        traj_b.append(float(lb))
    assert traj_a == traj_b, (traj_a, traj_b)


@pytest.mark.slow
def test_3d_parallel_block_data_sp_tp():
    """3-axis composition on a (data=2, sp=2, model=2) mesh: ring
    attention shards the SEQUENCE, Megatron column/row shards HEADS and
    MLP features, batch shards over data — outputs and grads must match
    the dense single-device math on the same full params."""
    from apex_tpu.transformer import ring_attention
    from jax import lax

    E, H, D = 16, 4, 4

    class Block3D(nn.Module):
        def __init__(self):
            super().__init__()
            self.q = tp.ColumnParallelLinear(E, E, input_grad_reduce=False)
            self.k = tp.ColumnParallelLinear(E, E, input_grad_reduce=False)
            self.v = tp.ColumnParallelLinear(E, E, input_grad_reduce=False)
            self.out = tp.RowParallelLinear(E, E)
            self.mlp = tp.ParallelMLP(E, 2 * E, activation="relu")

        def forward(self, p, x):
            B, T, _ = x.shape
            tpsz = tp._axis_size("model")
            hl = H // tpsz
            xf = tp.copy_to_model_parallel(x, "model")
            q = self.q(p["q"], xf).reshape(B, T, hl, D)
            k = self.k(p["k"], xf).reshape(B, T, hl, D)
            v = self.v(p["v"], xf).reshape(B, T, hl, D)
            q, k, v = (jnp.swapaxes(a, 1, 2) for a in (q, k, v))
            ctx = ring_attention(q, k, v, axis_name="sp")
            ctx = jnp.swapaxes(ctx, 1, 2).reshape(B, T, hl * D)
            x = x + self.out(p["out"], ctx)
            return x + self.mlp(p["mlp"], x)

    blk = Block3D()
    params, _ = blk.init(jax.random.PRNGKey(13))
    specs = tp.partition_specs(blk, params)
    devs = np.array(jax.devices()[:8]).reshape(2, 2, 2)
    mesh = Mesh(devs, ("data", "sp", "model"))
    rng = np.random.RandomState(13)
    x = jnp.asarray(rng.randn(4, 8, E) * 0.5, jnp.float32)

    xspec = P("data", "sp", None)
    y = jax.jit(jax.shard_map(
        lambda p, xb: blk(p, xb), mesh=mesh, in_specs=(specs, xspec),
        out_specs=xspec, check_vma=False))(params, x)

    # dense reference from the same full params
    def dense_ref(p, xb):
        def lin(pp, a):
            return a @ pp["weight"].T + pp.get("bias", 0.0)
        B, T, _ = xb.shape
        q = lin(p["q"], xb).reshape(B, T, H, D)
        k = lin(p["k"], xb).reshape(B, T, H, D)
        v = lin(p["v"], xb).reshape(B, T, H, D)
        q, k, v = (jnp.swapaxes(a, 1, 2) for a in (q, k, v))
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / (D ** 0.5)
        ctx = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
        ctx = jnp.swapaxes(ctx, 1, 2).reshape(B, T, E)
        xb = xb + lin(p["out"], ctx)
        h = jnp.maximum(lin(p["mlp"]["fc_in"], xb), 0.0)
        return xb + lin(p["mlp"]["fc_out"], h)

    y_ref = dense_ref(params, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=3e-5)

    # gradients through all three axes' collectives
    def loss_3d(p, xb):
        return jnp.sum(jnp.square(blk(p, xb)))

    def grad_3d(p, xb):
        g = jax.grad(loss_3d)(p, xb)
        # tokens are data- AND sp-sharded: grads of the (replicated)
        # params must be summed over both token-sharding axes, exactly
        # like DDP does over 'data' — TP-sharded leaves got their f/g
        # treatment inside the block already
        return jax.tree_util.tree_map(
            lambda t: lax.psum(lax.psum(t, "data"), "sp"), g)

    g_tp = jax.jit(jax.shard_map(
        grad_3d, mesh=mesh, in_specs=(specs, xspec), out_specs=specs,
        check_vma=False))(params, x)
    g_ref = jax.grad(lambda p: jnp.sum(jnp.square(dense_ref(p, x))))(
        params)
    _assert_trees_close(g_tp, g_ref, atol=5e-4)
