"""Tensor (model) parallelism: Megatron-style column/row-parallel layers.

The reference toolkit predates tensor parallelism (SURVEY.md §2.3: its
parallelism inventory is data-parallel only), but a TPU-native framework
scales BERT-large-class models across a mesh axis as a matter of course —
the mesh + collectives design (SURVEY.md §2.4) makes TP a module-level
concern rather than a runtime fork the way Megatron-LM's mpu is.

Pattern (Megatron-LM "Efficient Large-Scale Language Model Training",
applied the JAX way):

- ``ColumnParallelLinear`` — weight rows (output features) sharded over
  the ``model`` axis; forward is a local matmul producing the local slice
  of the output features.  No communication (optionally ``gather_output``
  all_gathers the feature axis).
- ``RowParallelLinear`` — weight columns (input features) sharded; each
  device contracts its input slice and the partial products are summed
  with ONE ``psum`` over the axis.  Bias is added after the reduction.
- ``ParallelMLP`` — Column(4E) -> activation -> Row(E): one psum per MLP.
- ``ParallelSelfAttention`` — q/k/v column-parallel with HEADS as the
  shard unit (contiguous head blocks, so a dim-0 split is exact), local
  flash/dense attention on the device's heads, row-parallel output
  projection: one psum per attention block.

How params flow (idiomatic GSPMD, not Megatron's per-rank allocation):
``init`` builds FULL-SIZE weights; :func:`partition_specs` walks the
module tree and returns a matching PartitionSpec pytree.  Jitting the
train step with ``jax.shard_map(..., in_specs=(specs, ...))`` (or
pjit-style sharding constraints) hands each device its local shard, and
the SAME forward code runs unmodified: inside shard_map the local
weight shard is simply a smaller array.  Outside any mesh (unit tests,
single device) the full weight is present and the psum no-ops via the
axis-in-scope check — the world_size==1 passthrough the reference's DDP
applies (apex/parallel/distributed.py world_size==1 branches).

Gradients: column/row shards receive local grads from the matmul
transposes; the replicated-activation psum transposes are inserted by
jax automatically.  Under a (data, model) mesh, DDP's
``allreduce_grads(axis_name="data")`` sums ONLY over the data axis, so
TP shards never get mixed across the model axis.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..nn.layers import Linear
from ..nn.module import Module, current_context
from ..nn import functional as F
from .sync_batchnorm import _axis_in_scope

__all__ = [
    "ColumnParallelLinear", "RowParallelLinear", "ParallelMLP",
    "ParallelSelfAttention", "VocabParallelEmbedding",
    "vocab_parallel_cross_entropy", "partition_specs",
    "local_shape", "sharded_optimizer_specs",
]

DEFAULT_AXIS = "model"


def _axis_size(axis_name: str) -> int:
    return lax.axis_size(axis_name) if _axis_in_scope(axis_name) else 1


# -- Megatron's conjugate f/g collectives -------------------------------
#
# Inside shard_map the loss is computed (identically) on every device of
# the model axis, so a plain ``psum`` at the row-parallel output would
# have its transpose re-sum the (already replicated) cotangent — every
# gradient upstream of it comes out axis_size times too large.  The
# correct pair (Megatron-LM's f/g):
#
#   g = reduce_from_model_parallel: psum forward, IDENTITY backward
#       (the cotangent of the replicated output is already replicated)
#   f = copy_to_model_parallel: identity forward, psum backward
#       (a replicated activation's gradient is the SUM of each shard's
#       local contribution)

@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _g_reduce(x, axis_name):
    return lax.psum(x, axis_name)


def _g_reduce_fwd(x, axis_name):
    return lax.psum(x, axis_name), None


def _g_reduce_bwd(axis_name, _res, ct):
    return (ct,)


_g_reduce.defvjp(_g_reduce_fwd, _g_reduce_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _f_copy(x, axis_name):
    return x


def _f_copy_fwd(x, axis_name):
    return x, None


def _f_copy_bwd(axis_name, _res, ct):
    return (lax.psum(ct, axis_name),)


_f_copy.defvjp(_f_copy_fwd, _f_copy_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _gather_last(x, axis_name):
    return lax.all_gather(x, axis_name, axis=x.ndim - 1, tiled=True)


def _gather_last_fwd(x, axis_name):
    return _gather_last(x, axis_name), x.shape[-1]


def _gather_last_bwd(axis_name, block, ct):
    # the replicated cotangent's transpose is SPLIT (take this device's
    # feature slice), not reduce-scatter — the all_gather transpose
    # would sum the identical replicated cotangents axis_size times
    idx = lax.axis_index(axis_name)
    return (lax.dynamic_slice_in_dim(ct, idx * block, block,
                                     axis=ct.ndim - 1),)


_gather_last.defvjp(_gather_last_fwd, _gather_last_bwd)


def reduce_from_model_parallel(x, axis_name: str = DEFAULT_AXIS):
    """psum forward / identity backward (Megatron's g)."""
    return _g_reduce(x, axis_name) if _axis_in_scope(axis_name) else x


def copy_to_model_parallel(x, axis_name: str = DEFAULT_AXIS):
    """identity forward / psum backward (Megatron's f)."""
    return _f_copy(x, axis_name) if _axis_in_scope(axis_name) else x


def gather_from_model_parallel(x, axis_name: str = DEFAULT_AXIS):
    """all_gather (last dim) forward / split backward."""
    return _gather_last(x, axis_name) if _axis_in_scope(axis_name) else x


class ColumnParallelLinear(Linear):
    """Linear whose OUTPUT features are sharded over ``axis_name``.

    Forward needs no collective: each device computes its slice of the
    output features from the (replicated) input.  ``gather_output=True``
    all_gathers the slices into the full feature dim (Megatron's
    gather_output flag) — leave False when a RowParallelLinear consumes
    the parallel activations directly.
    """

    def __init__(self, in_features: int, out_features: int,
                 bias: bool = True, gather_output: bool = False,
                 input_grad_reduce: bool = True,
                 axis_name: str = DEFAULT_AXIS):
        super().__init__(in_features, out_features, bias=bias)
        self.gather_output = gather_output
        # the f collective on the (replicated) input; blocks that feed
        # one activation into SEVERAL column layers (q/k/v) set this
        # False and apply copy_to_model_parallel once at block entry
        self.input_grad_reduce = input_grad_reduce
        self.axis_name = axis_name

    def param_specs(self) -> Dict[str, P]:
        s = {"weight": P(self.axis_name, None)}
        if self.use_bias:
            s["bias"] = P(self.axis_name)
        return s

    def forward(self, params, x):
        if self.input_grad_reduce:
            x = copy_to_model_parallel(x, self.axis_name)
        y = F.linear(x, params["weight"], params.get("bias"))
        if self.gather_output:
            y = gather_from_model_parallel(y, self.axis_name)
        return y


class RowParallelLinear(Linear):
    """Linear whose INPUT features are sharded over ``axis_name``.

    Each device contracts its input slice against its weight columns;
    the partial results are combined with one psum.  Bias (replicated)
    is added after the reduction so it is counted once.
    ``input_is_parallel=False`` first slices a replicated input down to
    this device's feature block (Megatron's scatter path).
    """

    def __init__(self, in_features: int, out_features: int,
                 bias: bool = True, input_is_parallel: bool = True,
                 axis_name: str = DEFAULT_AXIS):
        super().__init__(in_features, out_features, bias=bias)
        self.input_is_parallel = input_is_parallel
        self.axis_name = axis_name

    def param_specs(self) -> Dict[str, P]:
        s = {"weight": P(None, self.axis_name)}
        if self.use_bias:
            s["bias"] = P()
        return s

    def forward(self, params, x):
        in_scope = _axis_in_scope(self.axis_name)
        if not self.input_is_parallel and in_scope:
            # replicated input: each device slices its feature block; f
            # first, so the input's grad psums the zero-padded pieces
            # back into the full dense gradient
            x = copy_to_model_parallel(x, self.axis_name)
            tp = lax.axis_size(self.axis_name)
            idx = lax.axis_index(self.axis_name)
            block = self.in_features // tp
            x = lax.dynamic_slice_in_dim(x, idx * block, block,
                                         axis=x.ndim - 1)
        y = F.linear(x, params["weight"], None)
        # g: psum forward, identity backward — a plain psum's transpose
        # would re-sum the replicated cotangent (axis_size x grads)
        y = reduce_from_model_parallel(y, self.axis_name)
        b = params.get("bias")
        return y if b is None else y + b


class ParallelMLP(Module):
    """Column(hidden) -> activation -> Row(out): the Megatron MLP block,
    one psum per call."""

    def __init__(self, in_features: int, hidden_features: int,
                 activation: str = "gelu", bias: bool = True,
                 axis_name: str = DEFAULT_AXIS):
        super().__init__()
        self.fc_in = ColumnParallelLinear(in_features, hidden_features,
                                          bias=bias, axis_name=axis_name)
        self.fc_out = RowParallelLinear(hidden_features, in_features,
                                        bias=bias, axis_name=axis_name)
        self.activation = activation

    def forward(self, params, x):
        h = self.fc_in(params["fc_in"], x)
        h = getattr(F, self.activation)(h)
        return self.fc_out(params["fc_out"], h)


class ParallelSelfAttention(Module):
    """Self-attention with HEADS sharded over the model axis.

    q/k/v are separate column-parallel projections (contiguous head
    blocks shard exactly under a dim-0 split — a fused qkv matrix would
    interleave q/k/v inside one shard), the softmax(qk)v runs entirely
    locally on the device's heads via the same policy-aware
    ``dot_product_attention`` the single-device stack uses (flash kernel
    on TPU), and the output projection is row-parallel: ONE psum per
    attention block, the Megatron communication pattern.

    ``num_heads`` must divide by the axis size at run time.

    ``num_kv_heads < num_heads`` (GQA) shards the compact K/V
    projections over the same axis (``num_kv_heads % tp == 0``) and
    repeats them per local query-head group; ``rope_theta`` applies
    rotary position embeddings to q/k before attention (position-only,
    so head sharding is transparent) — together these are the Llama
    tensor-parallel block.
    """

    def __init__(self, embed_dim: int, num_heads: int, dropout: float = 0.0,
                 bias: bool = True, causal: bool = False,
                 attn_dropout: float = 0.0,
                 axis_name: str = DEFAULT_AXIS,
                 num_kv_heads: Optional[int] = None,
                 rope_theta: Optional[float] = None):
        super().__init__()
        if embed_dim % num_heads:
            raise ValueError(f"num_heads ({num_heads}) must divide "
                             f"embed_dim ({embed_dim})")
        self.embed_dim = embed_dim
        self.num_heads = num_heads
        self.num_kv_heads = (num_kv_heads if num_kv_heads is not None
                             else num_heads)
        if (self.num_kv_heads < 1
                or num_heads % self.num_kv_heads):
            raise ValueError(
                f"num_kv_heads={self.num_kv_heads} must be a positive "
                f"divisor of num_heads={num_heads}")
        self.head_dim = embed_dim // num_heads
        self.rope_theta = rope_theta
        self.causal = causal
        self.dropout_rate = dropout
        self.attn_dropout = attn_dropout    # attention-probs dropout
        self.axis_name = axis_name
        kv_dim = self.num_kv_heads * self.head_dim
        # one f at block entry instead of three: x feeds all three
        # projections, so input_grad_reduce is applied once in forward
        self.q = ColumnParallelLinear(embed_dim, embed_dim, bias=bias,
                                      input_grad_reduce=False,
                                      axis_name=axis_name)
        self.k = ColumnParallelLinear(embed_dim, kv_dim, bias=bias,
                                      input_grad_reduce=False,
                                      axis_name=axis_name)
        self.v = ColumnParallelLinear(embed_dim, kv_dim, bias=bias,
                                      input_grad_reduce=False,
                                      axis_name=axis_name)
        self.out = RowParallelLinear(embed_dim, embed_dim, bias=bias,
                                     axis_name=axis_name)

    def forward(self, params, x, mask: Optional[jax.Array] = None):
        from ..transformer.attention import dot_product_attention
        x = copy_to_model_parallel(x, self.axis_name)
        B, T, _ = x.shape
        tp = _axis_size(self.axis_name)
        if self.num_heads % tp or self.num_kv_heads % tp:
            raise ValueError(f"num_heads={self.num_heads} / num_kv_heads="
                             f"{self.num_kv_heads} not divisible by "
                             f"tensor-parallel size {tp}")
        h_local = self.num_heads // tp
        kv_local = self.num_kv_heads // tp
        q = self.q(params["q"], x).reshape(B, T, h_local, self.head_dim)
        k = self.k(params["k"], x).reshape(B, T, kv_local, self.head_dim)
        v = self.v(params["v"], x).reshape(B, T, kv_local, self.head_dim)
        q, k, v = (jnp.swapaxes(a, 1, 2) for a in (q, k, v))
        if self.rope_theta is not None:
            from ..models.llama import apply_rope
            q, k = apply_rope(q, k, jnp.arange(T), self.rope_theta)
        if kv_local != h_local:
            k = jnp.repeat(k, h_local // kv_local, axis=1)
            v = jnp.repeat(v, h_local // kv_local, axis=1)
        if (mask is not None and mask.ndim == 4
                and mask.shape[1] == self.num_heads and tp > 1):
            # per-head mask: take this device's head block, like the
            # weight shards (head-broadcast masks pass through untouched)
            idx = lax.axis_index(self.axis_name)
            mask = lax.dynamic_slice_in_dim(mask, idx * h_local, h_local,
                                            axis=1)
        attn_rng = None
        actx0 = current_context()
        if (self.attn_dropout > 0.0 and actx0 is not None and actx0.train):
            attn_rng = actx0.make_rng()
            if _axis_in_scope(self.axis_name):
                # independent attention-probs masks per head block
                attn_rng = jax.random.fold_in(
                    attn_rng, lax.axis_index(self.axis_name))
        ctx = dot_product_attention(
            q, k, v, mask=mask, causal=self.causal,
            dropout_rate=self.attn_dropout if attn_rng is not None else 0.0,
            dropout_rng=attn_rng)
        ctx = jnp.swapaxes(ctx, 1, 2).reshape(B, T, h_local * self.head_dim)
        actx = current_context()
        if self.dropout_rate > 0.0 and actx is not None and actx.train:
            key = actx.make_rng()
            if _axis_in_scope(self.axis_name):
                # decorrelate the mask across model-axis shards — the
                # apply-rng is replicated, and an identical mask on
                # every head/feature block is a different (stronger)
                # regularizer than the dense equivalent (same fix as
                # ulysses.py / ring_attention.py)
                key = jax.random.fold_in(key,
                                         lax.axis_index(self.axis_name))
            ctx = F.dropout(ctx, self.dropout_rate, key)
        return self.out(params["out"], ctx)


class VocabParallelEmbedding(Module):
    """Embedding with the VOCAB dimension sharded over the model axis —
    the largest single weight in BERT-class models (vocab x hidden).

    Each device holds a contiguous vocab block; a lookup masks ids
    outside its block to a local zero row, gathers, and the g-collective
    psum combines the one-hot contributions (exactly one device is
    nonzero per id).  Megatron's VocabParallelEmbedding as mesh
    collectives.
    """

    def __init__(self, num_embeddings: int, embedding_dim: int,
                 axis_name: str = DEFAULT_AXIS, init_std: float = 1.0):
        super().__init__()
        self.num_embeddings = num_embeddings
        self.embedding_dim = embedding_dim
        self.axis_name = axis_name
        self.init_std = init_std

    def create_params(self, key):
        return {"weight": self.init_std * jax.random.normal(
            key, (self.num_embeddings, self.embedding_dim), jnp.float32)}

    def param_specs(self) -> Dict[str, P]:
        return {"weight": P(self.axis_name, None)}

    def forward(self, params, ids):
        w = params["weight"]
        if not _axis_in_scope(self.axis_name):
            return F.embedding(ids, w)
        tp = lax.axis_size(self.axis_name)
        if self.num_embeddings % tp:
            raise ValueError(f"num_embeddings={self.num_embeddings} not "
                             f"divisible by tensor-parallel size {tp}")
        idx = lax.axis_index(self.axis_name)
        # derive the block from the actual local shard so a manually
        # padded table stays consistent with the mask math
        block = w.shape[0]
        start = idx * block
        local = ids - start
        in_block = (local >= 0) & (local < block)
        # F.embedding (not a raw take): an int8-quantized table
        # (quantization.QTensor) then gathers quantized rows and
        # dequantizes only those
        rows = F.embedding(jnp.where(in_block, local, 0), w)
        rows = jnp.where(in_block[..., None], rows, 0.0)
        return reduce_from_model_parallel(rows, self.axis_name)


def vocab_parallel_cross_entropy(local_logits: jax.Array,
                                 labels: jax.Array,
                                 axis_name: str = DEFAULT_AXIS,
                                 ignore_index: int = -100) -> jax.Array:
    """Cross-entropy over VOCAB-SHARDED logits without gathering them.

    ``local_logits``: (..., V/tp) — this device's vocab block (e.g. the
    output of a ColumnParallelLinear LM head with gather_output=False).
    The softmax statistics are combined with two scalar-per-token
    collectives (pmax for the stable max, psum for the normalizer) and
    the label's logit is picked out by the one device owning it —
    communication O(tokens), not O(tokens x vocab), Megatron's
    _VocabParallelCrossEntropy.  Masked tokens (``ignore_index``)
    contribute zero, mean over the rest.
    """
    f32 = local_logits.astype(jnp.float32)
    if _axis_in_scope(axis_name):
        tp = lax.axis_size(axis_name)
        idx = lax.axis_index(axis_name)
    else:
        tp, idx = 1, 0     # same masked math, degenerate collectives
    block = local_logits.shape[-1]
    start = idx * block
    # stable log-sum-exp across the sharded vocab; the max shift cancels
    # analytically, so its gradient path (incl. pmax) is cut explicitly
    local_max = lax.stop_gradient(jnp.max(f32, axis=-1))
    gmax = (lax.pmax(local_max, axis_name) if tp > 1 else local_max)
    sumexp = jnp.sum(jnp.exp(f32 - gmax[..., None]), axis=-1)
    # the partial-sum psum and the label-logit psum are both linear with
    # device-disjoint/identical-sum structure; plain psum would re-sum
    # the replicated cotangent in backward (the f/g issue), so both ride
    # the g-collective
    gsum = reduce_from_model_parallel(sumexp, axis_name)
    local_lbl = labels - start
    in_block = (local_lbl >= 0) & (local_lbl < block)
    picked = jnp.take_along_axis(
        f32, jnp.where(in_block, local_lbl, 0)[..., None], axis=-1)[..., 0]
    picked = jnp.where(in_block, picked, 0.0)
    label_logit = reduce_from_model_parallel(picked, axis_name)
    nll = jnp.log(gsum) + gmax - label_logit
    valid = labels != ignore_index
    nll = jnp.where(valid, nll, 0.0)
    denom = jnp.maximum(jnp.sum(valid.astype(jnp.float32)), 1.0)
    return jnp.sum(nll) / denom


def partition_specs(module: Module, params: Optional[Any] = None,
                    key: Optional[jax.Array] = None) -> Any:
    """PartitionSpec pytree matching ``module.init(...)[0]``.

    TP layers contribute their ``param_specs``; every other leaf is
    replicated (``P()``).  Pass the real ``params`` tree when you have
    it; otherwise the structure is derived shape-only via
    ``jax.eval_shape`` (no FLOPs, no memory).

    Use as the param entry of ``shard_map``'s in/out_specs, e.g.::

        specs = tensor_parallel.partition_specs(model)
        train = jax.jit(jax.shard_map(step, mesh=mesh,
                        in_specs=((specs, P(), P()), P("data")),
                        out_specs=((specs, P(), P()), P())))
    """
    if params is None:
        key = key if key is not None else jax.random.PRNGKey(0)
        params = jax.eval_shape(lambda k: module.init(k)[0], key)

    def build(mod: Module, p: Any) -> Any:
        if not isinstance(p, dict):
            return P()
        own = mod.param_specs() if hasattr(mod, "param_specs") else {}
        out = {}
        children = dict(mod.named_children())
        for name, sub in p.items():
            if name in own:
                out[name] = own[name]
            elif name in children:
                out[name] = build(children[name], sub)
            else:
                out[name] = jax.tree_util.tree_map(lambda _: P(), sub)
        return out

    return build(module, params)


def local_shape(shape, spec, mesh):
    """Per-device shape of a global array sharded by ``spec`` — via
    NamedSharding, which also rejects non-divisible dims with a clear
    error instead of silently floor-dividing.

    Public because it is the ONE global→local shape rule: the TP entry
    point derives its shard_map operand shapes through it and the
    static sharding propagator (``analysis.sharding``) owes its
    local-bytes accounting to the same arithmetic."""
    from jax.sharding import NamedSharding
    return NamedSharding(mesh, spec if spec is not None else P()
                         ).shard_shape(tuple(shape))


_local_shape = local_shape


def sharded_optimizer_specs(optimizer, params: Any, param_specs: Any,
                            mesh, axis_name: str = DEFAULT_AXIS) -> Any:
    """PartitionSpec tree for ``optimizer.init(params)``-shaped state
    under tensor-parallel sharding.

    Optimizer state must be built from the LOCAL param shards (the amp
    O2 wrapper keeps masters/moments as one flat buffer whose length is
    the per-device param count), so both ``init`` and ``step`` run
    inside ``shard_map`` — this derives the matching out/in specs:

    - a leaf whose local shape equals its global shape is replicated
      (scalars: step counters, loss scale);
    - a 1-D leaf that shrank is a flat per-device buffer — device-
      concat layout, ``P(axis_name)``;
    - a multi-dim leaf that shrank mirrors a sharded param (tree-state
      optimizers): the shrunken dims get ``axis_name``.

    Usage::

        ospecs = tp.sharded_optimizer_specs(opt, params, specs, mesh)
        opt_state = jax.jit(jax.shard_map(
            opt.init, mesh=mesh, in_specs=(specs,), out_specs=ospecs,
            check_vma=False))(params)
    """
    flat_params = jax.tree_util.tree_flatten_with_path(params)[0]
    spec_of = {jax.tree_util.keystr(p): s for p, s in
               jax.tree_util.tree_flatten_with_path(
                   param_specs, is_leaf=lambda x: isinstance(x, P))[0]}
    # spec inference for MIRRORED state leaves attributes every shrunken
    # dim to axis_name, so param_specs may only shard over that one axis
    # (the tensor-parallel case this helper exists for) — reject other
    # axes loudly rather than mis-shard silently
    for k, s in spec_of.items():
        for names in (s or ()):
            for n in (names if isinstance(names, tuple)
                      else (names,) if names is not None else ()):
                if n != axis_name:
                    raise ValueError(
                        f"param spec at {k} shards over axis {n!r}; "
                        f"sharded_optimizer_specs only supports specs "
                        f"over the single axis {axis_name!r}")
    local_params = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(params),
        [jax.ShapeDtypeStruct(
            _local_shape(l.shape,
                         spec_of.get(jax.tree_util.keystr(p)),  # None ok
                         mesh), l.dtype)
         for p, l in flat_params])

    glob = jax.eval_shape(optimizer.init, params)
    loc = jax.eval_shape(optimizer.init, local_params)

    # amp's flat buffers are stored at a block-aligned length
    # (_FlatLayout.storage), so a small model's local and global
    # buffers can have one shape while holding different elements:
    # whether they are sharded is read from the logical counts
    from ..amp._process_optimizer import FlatMasters
    flat_len = None
    if (isinstance(getattr(loc, "masters", None), FlatMasters)
            and glob.masters.layout.total != loc.masters.layout.total):
        flat_len = loc.masters.layout.storage

    def leaf_spec(g, l):
        if l.ndim == 1 and l.shape[0] == flat_len:
            return P(axis_name)
        if tuple(g.shape) == tuple(l.shape):
            return P()
        if l.ndim == 1:
            return P(axis_name)
        return P(*[axis_name if gs != ls else None
                   for gs, ls in zip(g.shape, l.shape)])

    # pair leaves positionally and unflatten on the LOCAL treedef: the
    # amp wrapper's FlatMasters node carries its layout (shapes/offsets)
    # as pytree aux data, which differs between the global and local
    # trees — a tree_map across the two would reject the mismatch, and
    # shard_map's out_specs must match the structure the mapped init
    # actually returns (the local one)
    gl = jax.tree_util.tree_leaves(glob)
    ll, ldef = jax.tree_util.tree_flatten(loc)
    if len(gl) != len(ll):
        raise ValueError(
            f"optimizer state leaf count differs between global "
            f"({len(gl)}) and local ({len(ll)}) init — cannot infer "
            f"sharded state specs for this optimizer")
    return jax.tree_util.tree_unflatten(
        ldef, [leaf_spec(g, l) for g, l in zip(gl, ll)])
