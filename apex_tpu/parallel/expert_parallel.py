"""Expert parallelism: Mixture-of-Experts over a mesh axis.

The reference predates MoE entirely; this completes apex_tpu's
parallelism surface (dp/tp/pp/sp/ep).  One layer, two dispatches:

**Local (no ``expert`` axis in scope): sorted and grouped.**  The router
scores every token over all ``n_experts``; the ``T*k`` assignments are
sorted by expert (stable, choice-major: every token's first choice queues
before any token's second); the rows of the experts this layer *holds*
(``experts_held`` = a start and a count, default all of them) are
gathered into one row buffer of a static size; the experts run as grouped
matrix products over the contiguous row groups (on the chip the Mosaic
kernels of ``ops/pallas_grouped_matmul.py``, which visit no tile outside
every group; ``lax.ragged_dot`` off it and for shapes they do not take);
and the rows come home weighted by the gates, summed in fp32 over a token's
assignments and rounded once.  A row goes out by the sort, ``x2d[token]``.
The way home has two forms, chosen from shapes (``ops/row_moves.py``).
Where the ``k`` slots a token may fill are few beside the buffer's rows (a
layer that holds a quarter of the experts or more at twice the expected
rows), it is a gather too, by the sort's inverse (``_queue_positions``:
where each assignment lies in the buffer): a token's ``k`` rows gathered and
summed in one pass, each move the other's transpose under a ``custom_vjp``,
so no scatter-add, a read-modify-write of HBM a row at a time, is traced for
the rows in either direction.  Where most slots would be empty (a sixteenth
of the experts held) it is ``zeros.at[token].add``, whose cost follows the
buffer's rows and not the slots.  Nothing of a ``tokens x experts x slots``
shape exists (the rows gathered home are ``k x tokens``).  Assignments to
experts held elsewhere add nothing here — that is the layer of ONE chip of an
expert-parallel group, without its exchange.  With ``capacity_factor=None``
nothing is dropped; a capacity (``ceil(cf * T * k / E)`` rows an expert)
drops what queues behind it, as Switch does.  The row buffer is
``T * min(k, held)`` rows (it cannot overflow) unless
``row_buffer_factor`` sizes it as that multiple of the expected rows,
``T * k * held / E``; what overflowed it is counted with the dropped.

**Across an ``expert`` mesh axis: GShard/Switch in shard_map form.**  The
axis shards BOTH the tokens (data-style) and the expert homes: device d
holds tokens-shard d and experts ``[d*E/ep, (d+1)*E/ep)``; each device
routes its local tokens (replicated router weights), builds a
capacity-bounded dispatch tensor, and one ``all_to_all`` ships every token
to the device owning its expert; the expert MLPs run as one vmapped batch;
the reverse ``all_to_all`` brings results home, where the gate-weighted
combine reads them back.  This path keeps the ``(T, E, C)`` masks and
needs a capacity.  Two all_to_alls forward — their transposes are
all_to_alls again, so backward needs no f/g correction the way psum-based
TP does.

Router: ``"softmax"`` — top-1 (Switch) by default; ``top_k=2`` with
``expert_type="swiglu"`` gives the Mixtral shape (renormalized gate
weights, SwiGLU experts) — or ``"sigmoid"``: independent scores, the k
largest renormalized to sum 1 and scaled by ``routed_scaling``.
``router_bias`` adds a selection bias ``expert_bias`` (E,): the choice is
the k largest of ``score + bias``, the weights are the chosen scores alone
(over their sum + 1e-6); the bias takes no gradient (the balancing rule that
would move it is not part of this layer).  The
auxiliary load-balancing loss (Switch eq. 4: E * sum_e f_e * P_e, fraction
counted over all k assignments) is returned by ``forward`` when
``return_aux_loss`` — add ``aux_weight * aux`` to the task loss.
``shared_hidden`` adds an expert of the routed experts' own kind (SwiGLU
where they are gated, ``activation`` between two matrices where they are
not) that every token passes through.

The work is written under the scopes ``moe.route`` / ``moe.dispatch`` /
``moe.experts`` / ``moe.combine`` (observability/phases.py), and
``return_stats`` hands back the counters :data:`MOE_COUNTERS`.  What a
traced layer is built as goes to the registry on the host, while the
program is traced (no output of the program): ``moe_router_calls_total
{router, top_k}`` (and ``moe_router_bias_calls_total`` for the layers with a
selection bias), ``moe_row_buffer_rows_total``, and
``moe_experts_held_total`` beside ``moe_router_experts_total``, whose ratio
says which share of an expert-parallel group a step is, and
``moe_grouped_dot_calls_total{impl, tile}``, what implements each grouped
product, and ``moe_row_move_calls_total{impl, move}``, what moves the rows
(docs/observability.md).
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..nn.module import Module
from ..nn import functional as F
from .sync_batchnorm import _axis_in_scope

__all__ = ["ExpertParallelMLP", "allreduce_replicated_grads",
           "MOE_COUNTERS", "record_moe_counters"]

DEFAULT_AXIS = "expert"
# per step and layer-reduced (ExpertParallelMLP.reduce_stats): assignments
# that landed on an expert held here; the most rows any held expert got;
# assignments lost to a capacity or to the row buffer (dropless: 0)
MOE_COUNTERS = ("moe_assignments_held", "moe_expert_load_max",
                "moe_dropped_assignments")


def _swiglu(x, w_gate, w_in, w_out):
    return (F.silu(x @ w_gate.astype(x.dtype))
            * (x @ w_in.astype(x.dtype))) @ w_out.astype(x.dtype)


def _queue_positions(key, starts, n: int, rows: int):
    """Where the stable sort by ``key`` puts each assignment, without the
    sort: ``(at, place)``, both (A,) int32; ``place`` an assignment's rank
    among those of its key, ``at = starts[key] + place`` its row of the
    buffer, -1 where ``key`` is not under ``n`` (held elsewhere) or the row
    not under ``rows`` (past the buffer's end).  It is the inverse of
    ``argsort(key, stable=True)[:rows]`` and is written as no scatter and no
    second sort: the ranks are running counts of a one-hot ``(n, A)``, taken
    by two products with triangles of ones, inside blocks of 128 and over
    the blocks' totals (0/1 and counts to 128 in bf16, sums in fp32: exact)."""
    A, lanes = key.shape[0], 128
    blocks = -(-A // lanes)
    key = jnp.pad(key, (0, blocks * lanes - A), constant_values=n)
    hot = key[None, :] == jnp.arange(n)[:, None]                  # (n, A)

    def ones_above(m, strict):
        i = jnp.arange(m)
        return ((i[:, None] < i[None, :]) if strict
                else (i[:, None] <= i[None, :])).astype(jnp.bfloat16)

    inside = jnp.einsum("nbl,lm->nbm",
                        hot.astype(jnp.bfloat16).reshape(n, blocks, lanes),
                        ones_above(lanes, False),
                        preferred_element_type=jnp.float32)
    before = jnp.einsum("nb,bc->nc", inside[..., -1].astype(jnp.bfloat16),
                        ones_above(blocks, True),
                        preferred_element_type=jnp.float32)
    count = (inside + before[..., None]).reshape(n, -1)     # inclusive, own key
    place = jnp.sum(jnp.where(hot, count - 1.0, 0.0), axis=0).astype(jnp.int32)
    at = place + starts[jnp.minimum(key, n - 1)]
    at = jnp.where((key < n) & (at < rows), at, -1)
    return at[:A], place[:A]


class ExpertParallelMLP(Module):
    """Top-k routed MoE MLP; experts sharded over ``axis_name``.

    Params: ``router`` (d, E) ((E, d) with ``router_out_in``; and
    ``expert_bias`` (E,) with ``router_bias``) replicated and kept fp32
    under amp; ``w_in``
    (n, d, hidden) and ``w_out`` (n, hidden, d) for the ``n`` experts held
    (all E by default), sharded on the expert dim (see ``param_specs``);
    gated experts add ``w_gate`` (n, d, hidden); ``shared_hidden`` adds
    ``shared`` = {w_in, w_out} (and w_gate where the experts are gated)
    without the expert dim.
    Call inside shard_map with tokens sharded over the same axis;
    outside any mesh the experts held run locally (module docstring).

    ``top_k=1`` is Switch (gate = raw top-1 prob).  ``top_k>1`` is the
    GShard/Mixtral shape: each token goes to its k best experts, gate
    weights renormalized to sum 1 over the chosen k; under a capacity,
    slots are assigned first-choice-first (every token's first choice
    queues before any token's second), so under pressure second choices
    drop first.  ``expert_type="swiglu"`` makes each expert the Llama MLP
    ``(silu(x@w_gate) * (x@w_in)) @ w_out`` (Mixtral's expert).
    """

    fp32_param_names = ("router", "expert_bias")

    def __init__(self, embed_dim: int, hidden_dim: int, n_experts: int,
                 capacity_factor: Optional[float] = 1.25,
                 activation: str = "gelu",
                 axis_name: str = DEFAULT_AXIS,
                 top_k: int = 1,
                 expert_type: str = "mlp",
                 router_type: str = "softmax",
                 routed_scaling: float = 1.0,
                 experts_held: Optional[Tuple[int, int]] = None,
                 shared_hidden: Optional[int] = None,
                 row_buffer_factor: Optional[float] = None,
                 router_bias: bool = False,
                 router_out_in: bool = False):
        super().__init__()
        if not 1 <= top_k <= n_experts:
            raise ValueError(f"top_k={top_k} not in [1, {n_experts}]")
        if expert_type not in ("mlp", "swiglu"):
            raise ValueError(f"unknown expert_type {expert_type!r}")
        if router_type not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown router_type {router_type!r}")
        start, count = experts_held or (0, n_experts)
        if not (0 <= start and count >= 1 and start + count <= n_experts):
            raise ValueError(f"experts_held={experts_held} not inside "
                             f"[0, {n_experts})")
        self.embed_dim = embed_dim
        self.hidden_dim = hidden_dim
        self.n_experts = n_experts
        self.capacity_factor = capacity_factor
        self.activation = activation
        self.axis_name = axis_name
        self.top_k = top_k
        self.expert_type = expert_type
        self.router_type = router_type
        self.routed_scaling = routed_scaling
        self.held_start, self.n_held = start, count
        self.shared_hidden = shared_hidden
        self.row_buffer_factor = row_buffer_factor
        self.router_bias = router_bias
        # the ``router`` leaf as (E, d), torch's (out, in), and not (d, E)
        self.router_out_in = router_out_in

    def create_params(self, key):
        k1, k2, k3, k4, k5 = jax.random.split(key, 5)
        d, h, E, n = (self.embed_dim, self.hidden_dim, self.n_experts,
                      self.n_held)
        s_in = (2.0 / d) ** 0.5
        s_out = (2.0 / h) ** 0.5
        p = {
            "router": jax.random.normal(
                k1, (E, d) if self.router_out_in else (d, E),
                jnp.float32) * 0.02,
            "w_in": jax.random.normal(k2, (n, d, h), jnp.float32) * s_in,
            "w_out": jax.random.normal(k3, (n, h, d), jnp.float32) * s_out,
        }
        if self.expert_type == "swiglu":
            p["w_gate"] = (jax.random.normal(k4, (n, d, h), jnp.float32)
                           * s_in)
        if self.router_bias:
            p["expert_bias"] = jnp.zeros((E,), jnp.float32)
        if self.shared_hidden:
            hs = self.shared_hidden
            ks = jax.random.split(k5, 3)
            p["shared"] = {
                "w_in": jax.random.normal(ks[1], (d, hs)) * s_in,
                "w_out": jax.random.normal(ks[2], (hs, d))
                * (2.0 / hs) ** 0.5}
            if self.expert_type == "swiglu":
                p["shared"]["w_gate"] = (jax.random.normal(ks[0], (d, hs))
                                         * s_in)
        return p

    def param_specs(self) -> Dict[str, P]:
        s = {"router": P(),
             "w_in": P(self.axis_name, None, None),
             "w_out": P(self.axis_name, None, None)}
        if self.expert_type == "swiglu":
            s["w_gate"] = P(self.axis_name, None, None)
        if self.router_bias:
            s["expert_bias"] = P()
        if self.shared_hidden:
            s["shared"] = {k: P() for k in ("w_gate", "w_in", "w_out")
                           if k != "w_gate" or self.expert_type == "swiglu"}
        return s

    def capacity(self, n_tokens: int) -> Optional[int]:
        """Rows an expert takes of ``n_tokens`` tokens' ``top_k``
        assignments each; None when nothing is dropped."""
        if self.capacity_factor is None:
            return None
        return max(1, math.ceil(self.capacity_factor * n_tokens
                                * self.top_k / self.n_experts))

    # -- routing ----------------------------------------------------------
    def _router(self, params) -> jax.Array:
        """The router's (d, E) matrix, however the leaf is kept."""
        return params["router"].T if self.router_out_in else params["router"]

    def _route(self, x2d: jax.Array, router: jax.Array, want_aux: bool,
               bias: Optional[jax.Array] = None):
        """(gates (T, k) fp32, experts (T, k) int32, aux loss) — scores in
        fp32 over all ``n_experts``, whatever this layer holds.  With a
        selection ``bias`` (E,) the choice reads ``score + bias`` and the
        weights the scores alone."""
        E, k = self.n_experts, self.top_k
        logits = x2d.astype(jnp.float32) @ router.astype(jnp.float32)
        if self.router_type == "sigmoid":
            probs = jax.nn.sigmoid(logits)
        else:
            probs = jax.nn.softmax(logits, axis=-1)
        if bias is None:
            gates, experts = lax.top_k(probs, k)               # (T,k)
            if k > 1 or self.router_type == "sigmoid":
                # Mixtral: gate weights renormalized over the chosen k
                gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
        else:
            _, experts = lax.top_k(lax.stop_gradient(
                probs + bias.astype(jnp.float32)), k)
            gates = jnp.take_along_axis(probs, experts, axis=-1)
            gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-6)
        gates = gates * self.routed_scaling
        aux = 0.0
        if want_aux:
            # Switch aux loss (eq. 4), fraction over all k assignments:
            # f_e x mean prob P_e, scaled E; reduces to Switch at k=1
            onehot = jax.nn.one_hot(experts, E, dtype=jnp.float32)
            f_e = jnp.mean(jnp.sum(onehot, axis=1), axis=0) / k
            aux = E * jnp.sum(f_e * jnp.mean(probs, axis=0))
        return gates, experts, aux

    def _dispatch(self, x2d: jax.Array, router: jax.Array, capacity: int
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """The all_to_all path's (dispatch (T,E,C) one-hot, combine (T,E,C)
        gate-weighted, aux load-balance loss) for the local token block."""
        T = x2d.shape[0]
        E, k = self.n_experts, self.top_k
        gates, experts, aux = self._route(x2d, router, True)
        onehot = jax.nn.one_hot(experts, E, dtype=jnp.float32)  # (T,k,E)
        # queue positions, choice-major: every token's 1st choice is
        # enqueued before any token's 2nd, so overflow drops 2nd picks
        ohf = jnp.swapaxes(onehot, 0, 1).reshape(k * T, E)
        pos = jnp.cumsum(ohf, axis=0) * ohf - 1.0              # (kT,E)
        keep = (pos >= 0) & (pos < capacity)
        disp = ohf * keep                                      # (kT,E)
        posc = jax.nn.one_hot(
            jnp.sum(pos * ohf, -1).astype(jnp.int32), capacity,
            dtype=jnp.float32)                                 # (kT,C)
        per_choice = (disp[:, :, None]
                      * posc[:, None, :]).reshape(k, T, E, capacity)
        # slots are disjoint across choices, so the union is a sum
        dispatch = jnp.sum(per_choice, axis=0)                 # (T,E,C)
        combine = jnp.einsum("ktec,tk->tec", per_choice, gates)
        return dispatch, combine, aux

    def _expert_mlp(self, params, xe):
        """xe: (E_local, S, d) -> (E_local, S, d), vmapped over experts."""
        if self.expert_type == "swiglu":
            return jax.vmap(lambda g, i, o, t: _swiglu(t, g, i, o))(
                params["w_gate"], params["w_in"], params["w_out"], xe)
        act = getattr(F, self.activation)

        def one(w_in, w_out, t):
            return act(t @ w_in.astype(t.dtype)) @ w_out.astype(t.dtype)

        return jax.vmap(one)(params["w_in"], params["w_out"], xe)

    def _grouped_mlp(self, params, xs, group_sizes, live):
        """xs: (R, d) rows sorted by expert, ``group_sizes`` (n,) rows each
        expert held takes from the front, ``live`` (R, 1) the rows inside a
        group -> (R, d).  On the chip a product is
        ``ops.pallas_grouped_matmul`` wherever its tile chooser takes the
        shapes: the kernel owns the rows outside every group (zeros in its
        results, never read into a live row), accumulates in fp32 and
        rounds once.  Elsewhere it is ``lax.ragged_dot``, which need not
        write (nor, transposed, read) those rows, so they are forced to
        zero on both sides of it."""
        from ..ops import dispatch, pallas_grouped_matmul as pgm
        rows = xs.shape[0]
        items = {}              # the kernels' work items, once a row tile

        def gdot(t, w):
            tile = (pgm.row_tile(rows, *w.shape[1:], w.shape[0], w.dtype)
                    if dispatch.pallas_enabled() else 0)
            if tile:
                if tile not in items:
                    items[tile] = pgm.work_items(group_sizes, rows, tile)
                return pgm.grouped_matmul(t.astype(w.dtype), w, items[tile],
                                          tile).astype(t.dtype)
            pgm.count_product("ragged_dot", 0)
            y = lax.ragged_dot(jnp.where(live, t, 0).astype(w.dtype), w,
                               group_sizes,
                               preferred_element_type=jnp.float32)
            return jnp.where(live, y, 0).astype(t.dtype)

        if self.expert_type == "swiglu":
            h = F.silu(gdot(xs, params["w_gate"])) * gdot(xs, params["w_in"])
        else:
            h = getattr(F, self.activation)(gdot(xs, params["w_in"]))
        return gdot(h, params["w_out"])

    def _shared(self, p, x2d):
        """The expert every token passes through, in the routed experts'
        kind."""
        if self.expert_type == "swiglu":
            return _swiglu(x2d, **p)
        h = getattr(F, self.activation)(x2d @ p["w_in"].astype(x2d.dtype))
        return h @ p["w_out"].astype(x2d.dtype)

    def _sorted_forward(self, params, x2d, want_aux):
        """The local path: (y (T, d), aux, counters)."""
        from ..ops import row_moves
        T, d = x2d.shape
        k, n = self.top_k, self.n_held
        rows = T * min(k, n)
        if self.row_buffer_factor is not None:
            want = math.ceil(self.row_buffer_factor * T * k * n
                             / self.n_experts)
            rows = min(rows, -(-want // 8) * 8)
        self._count_traced_layer(rows)
        with jax.named_scope("moe.route"):
            gates, experts, aux = self._route(
                x2d, self._router(params), want_aux,
                params["expert_bias"] if self.router_bias else None)
        with jax.named_scope("moe.dispatch"):
            # choice-major queue: assignment a = choice * T + token
            local = experts.T.reshape(-1) - self.held_start
            key = jnp.where((local >= 0) & (local < n), local, n)
            sizes = jnp.sum(key[:, None] == jnp.arange(n)[None, :], axis=0,
                            dtype=jnp.int32)                   # (n,)
            held = jnp.sum(sizes)
            order = jnp.argsort(key, stable=True)[:rows]
            key_s = key[order]
            starts = jnp.cumsum(sizes) - sizes
            place = jnp.arange(rows) - starts[jnp.minimum(key_s, n - 1)]
            cap = self.capacity(T)
            live = key_s < n
            kept = live if cap is None else live & (place < cap)
            # a group ends where the buffer does; rows over the capacity
            # stay in their group and weigh nothing
            sizes_in = jnp.clip(rows - starts, 0, sizes)
            weight = jnp.where(kept, gates.T.reshape(-1)[order], 0.0)
            token = order % T
            by_gathers = row_moves.home_by_gathers(T * k, rows)
            if by_gathers:
                # the same by assignment: where the sort puts each, and its
                # place in its expert's queue; the way home is a gather by it
                at, queued = _queue_positions(key, starts, n, rows)
                fits = at >= 0 if cap is None else (at >= 0) & (queued < cap)
                gates_kept = jnp.where(fits.reshape(k, T).T, gates, 0.0)
                xs = row_moves.gather(x2d, token, at)
            else:
                # (with the transposes autodiff writes of the two lines)
                row_moves.count_move("gather", "rows_from_tokens", 2)
                row_moves.count_move("scatter_add", "tokens_from_rows", 2)
                xs = x2d[token]
            stats = {"moe_assignments_held": held,
                     "moe_expert_load_max": jnp.max(sizes),
                     "moe_dropped_assignments": held - jnp.sum(kept)}
        with jax.named_scope("moe.experts"):
            ys = self._grouped_mlp(params, xs, sizes_in, live[:, None])
            shared = (self._shared(params["shared"], x2d)
                      if self.shared_hidden else None)
        with jax.named_scope("moe.combine"):
            if by_gathers:
                y = row_moves.combine(ys, gates_kept, shared, token, weight,
                                      at)
            else:
                y = jnp.zeros((T, d), jnp.float32).at[token].add(
                    ys.astype(jnp.float32) * weight[:, None])
                if shared is not None:
                    y = y + shared.astype(jnp.float32)
                y = y.astype(x2d.dtype)
        return y, aux, stats

    def _count_traced_layer(self, rows: int) -> None:
        """Host side, once a trace of the sorted dispatch: the router's
        kind, the row buffer it was built with and the share it holds."""
        from ..observability.metrics import get_registry
        reg = get_registry()
        reg.counter("moe_router_calls_total",
                    help="sorted-dispatch expert layers traced, by the "
                    "router's score function and experts a token").labels(
                        router=self.router_type, top_k=str(self.top_k)).inc()
        if self.router_bias:
            reg.counter("moe_router_bias_calls_total",
                        help="of moe_router_calls_total, the layers whose "
                        "choice reads a selection bias").inc()
        for name, value, what in (
                ("moe_row_buffer_rows_total", rows, "rows of the row buffers"),
                ("moe_experts_held_total", self.n_held, "experts held by"),
                ("moe_router_experts_total", self.n_experts,
                 "experts scored by the routers of")):
            reg.counter(name, help=f"{what} the sorted-dispatch expert "
                        "layers traced").inc(value)

    @staticmethod
    def reduce_stats(stats):
        """One step's counters from its layers' (``MOE_COUNTERS``)."""
        pick = lambda name: jnp.stack([s[name] for s in stats])
        return {"moe_assignments_held": jnp.sum(pick("moe_assignments_held")),
                "moe_expert_load_max": jnp.max(pick("moe_expert_load_max")),
                "moe_dropped_assignments":
                    jnp.sum(pick("moe_dropped_assignments"))}

    def forward(self, params, x, return_aux_loss: bool = False,
                return_stats: bool = False):
        *lead, d = x.shape
        x2d = x.reshape(-1, d)
        T = x2d.shape[0]
        E = self.n_experts
        ep = (lax.axis_size(self.axis_name)
              if _axis_in_scope(self.axis_name) else 1)
        if E % ep:
            raise ValueError(f"n_experts={E} not divisible by expert-"
                             f"parallel size {ep}")
        if ep == 1:
            y2d, aux, stats = self._sorted_forward(params, x2d,
                                                   return_aux_loss)
        else:
            y2d, aux = self._all_to_all_forward(params, x2d, ep)
            stats = None
        out = (y2d.reshape(*lead, d),)
        if return_aux_loss:
            out += (aux,)
        if return_stats:
            out += (stats,)
        return out if len(out) > 1 else out[0]

    def _all_to_all_forward(self, params, x2d, ep):
        T, d = x2d.shape
        E, e_loc = self.n_experts, self.n_experts // ep
        if (self.capacity_factor is None or self.n_held != E
                or self.shared_hidden or self.router_bias):
            raise NotImplementedError(
                "the all_to_all dispatch needs a capacity_factor and has "
                "no experts_held / shared expert / selection bias; the "
                "sorted dispatch is "
                "not exchanged across an expert axis yet")
        capacity = self.capacity(T)
        dispatch, combine, aux = self._dispatch(x2d, self._router(params),
                                                capacity)
        # (T,E,C) x (T,d) -> (E,C,d): the local contribution per expert
        sent = jnp.einsum("tec,td->ecd", dispatch.astype(x2d.dtype), x2d)
        # (E,C,d) -> (ep, e_loc, C, d) -all_to_all-> every device
        # ends up with ITS experts' queues from all source devices
        sent = sent.reshape(ep, e_loc, capacity, d)
        recv = lax.all_to_all(sent, self.axis_name, split_axis=0,
                              concat_axis=0, tiled=False)
        # (ep_src, e_loc, C, d) -> (e_loc, ep_src*C, d)
        xe = jnp.moveaxis(recv, 0, 1).reshape(e_loc, ep * capacity, d)
        ye = self._expert_mlp(params, xe)
        back = jnp.moveaxis(ye.reshape(e_loc, ep, capacity, d), 1, 0)
        got = lax.all_to_all(back, self.axis_name, split_axis=0,
                             concat_axis=0, tiled=False)
        got = got.reshape(E, capacity, d)
        return (jnp.einsum("tec,ecd->td", combine.astype(got.dtype), got),
                aux)


def record_moe_counters(metrics, registry=None) -> None:
    """Host side: keep a step's :data:`MOE_COUNTERS` (those its metrics
    hold) as gauges of the observability registry."""
    from ..observability.metrics import get_registry
    reg = registry or get_registry()
    for name in MOE_COUNTERS:
        if name in metrics:
            reg.gauge(name, help="expert layers of the last step, see "
                      "parallel/expert_parallel.py").set(
                          float(metrics[name]))


def allreduce_replicated_grads(grads, specs, axis_name: str):
    """DDP-style psum over ``axis_name`` for the REPLICATED leaves only.

    With experts sharded over the token/data axis (DeepSpeed-MoE
    style), expert-sharded leaves (their spec mentions ``axis_name``)
    hold that device's own experts' grads — a blanket psum would be
    wrong for them, while router/attention/norm grads are data-parallel
    and need the usual sum.  ``specs`` is the
    ``tensor_parallel.partition_specs(model)`` tree.
    """
    def names_in(spec):
        out = set()
        for part in spec:
            if part is None:
                continue
            out.update(part if isinstance(part, tuple) else (part,))
        return out

    def red(g, s):
        return g if axis_name in names_in(s) else lax.psum(g, axis_name)

    return jax.tree_util.tree_map(
        red, grads, specs,
        is_leaf=lambda x: isinstance(x, P))
