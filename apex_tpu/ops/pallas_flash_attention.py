"""Pallas blocked flash attention (fwd + bwd) for the MXU.

New capability relative to the reference (2019, pre-attention — SURVEY.md
§5): apex_tpu treats transformer workloads as first-class.  This kernel
is the compute core of ``transformer.dot_product_attention`` and, through
it, ``ulysses_attention``'s per-head local attention.  (Ring attention
keeps its own jnp online-softmax accumulation: its inner blocks interleave
with ppermutes and XLA fuses them against the collective.)

Design (FlashAttention-style, true blocked form): each of the three
kernels runs a grid (batch*heads / hb, rows, steps) whose last axis is
sequential ("arbitrary"); a step takes ``hb`` heads of one batch entry
(``_heads_per_step``), which share its masks, its fixed cost and, where
K/V heads are shared, their K/V block.  A row
accumulates into one output block in VMEM
scratch while the blocks of the other side are *streamed* past it one
(BLK, D) block a step — nothing scales with T in VMEM: K and V past a q
block in the forward (running max m, running sum l, unnormalized
accumulator) and in the dQ pass, Q and dO past a k block in the dK/dV
pass.  The forward emits the per-row logsumexp; the backward recomputes
probabilities from it, a handful of MXU contractions per block pair.

Which pairs a grid visits is ``_Sweep``'s: every pair without a mask on
position; under ``causal`` the triangle, folded so that a long row and a
short one share n + 1 steps and no step is dead; with a sliding ``window``
(key j visible to query i iff i - W < j <= i) only the ``_band_blocks``
blocks a row of blocks can see, so the cost of a windowed layer is linear
in T.  A visited pair is *interior* when every one of its scores is
visible (below the diagonal, inside the band, no padded key, no
``kv_mask``, segments or dropout) and runs a body with no iota, compare
or select; an *edge* pair runs the masked body.  The counters
``flash_block_pairs_total{kind}``, ``flash_pad_copies_total`` and
``flash_calls_total{layout, kv[, rope]}`` say at trace time what a call's
grids visit, what it copies and which form its operands took.

Operands come in one of two forms (``_Layout``), named by the entry the
caller takes, and the kernels address both through the block shape and the
index map of a spec and one accessor for "head h of this block":
head-major, ``flash_attention`` on (B, H, T, D), a step's ``hb`` heads the
leading ``hb`` of a (hb, blk, D) block of the (B*H, T, D) fold; and
token-major, ``flash_attention_token_major`` on (B, T, H, D), which is a
projection's own (B, T, H*D) output, a step's heads ``hb`` lane tiles of a
(1, blk, hb*D) block (D a multiple of 128).  In both, K and V may come at
fewer heads than q (``Hkv`` dividing ``H``, read from the shapes): a
step's heads are then of one group and fetch its one K/V block, and dk/dv
adds the group's query heads up in its fp32 accumulator and writes each
K/V head once.  A caller with token-major, grouped operands moves no axis
and repeats no head on the way in or out; a head-major call with K/V at
q's head count launches what it launched before there was a choice.

A score head may be wider than the value head by half a lane tile (a
latent-attention head: 192 = 128 + 64 against values of 128).  It comes in
two parts, each where its projection wrote it: q and k at the value head's
width, and the trailing ``_ROPE`` = 64 numbers as ``q_rope`` (B, T, H*64)
and ``k_rope``, one head for all query heads (B, T, 64).  A score is
``q . k + q_rope . k_rope``: two contractions a head, the second against a
(blk, 128) tile that holds the rope keys in the half its head reads and
zeros in the other, so that two heads share a lane tile of ``q_rope`` and no
operand is padded or sliced off a lane boundary.  dq's rope part leaves the
dq kernel beside dq; dk's leaves the dk/dv kernel as one float32 partial sum
a grid step (its ``hb`` heads), which ``_bwd`` adds up over the steps of a
batch entry: the gradient of the one head is the sum over all query heads.
Token-major only, K/V at q's head count, an even number of heads a step.

Operands reach the kernels as they are when T is a whole number of blocks
and D is under 128 or a multiple of it (a block's last dimension may equal
the array's): no pad before, no slice after.  Per-row statistics cross HBM
as (8, T) sublane-broadcast row tiles (lse, delta; the layout of the
key-validity and segment-id tiles).  Where queries lie along a score
tile's sublanes (forward, dQ) they are widened once a row into
(rows, 128) tiles with every lane equal — the form of the m/l scratch,
which meets the score tile as whole copies of its vregs and the
accumulator as it is, never as a (rows, 1) column.  The dK/dV pass is
computed transposed (scores k-major), so a query's statistics stay rows,
and p and ds are born as the left operands of dV = P^T dO and dK = dS^T Q.

Matmuls feed the MXU in the input dtype (bf16 stays bf16) with fp32
accumulation via ``preferred_element_type``; softmax state is always
fp32.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_common import LANES, interpret, token_tile_axes

# checkpoint_name tags on the forward kernel's two results, given inside
# the forward rule so that the residuals the backward rule reads are the
# named values themselves: a remat policy that saves these names
# (models/_remat.py) keeps o and lse, and the backward of a rematerialized
# block does not launch flash_fwd again.  Outside a jax.checkpoint a name
# is the identity and lowers to nothing.
FLASH_OUT_NAME = "flash_o"
FLASH_LSE_NAME = "flash_lse"

_VMEM_BUDGET = 12 * 1024 * 1024
_BLK = 512          # q/k rows per block (clamped to the padded seq len)
_NEG = -1e30
_ROPE = 64          # a score head's trailing part: half a lane tile


def _dot(a, b, contract):
    """MXU contraction with fp32 accumulation.  Precision is pinned here
    rather than inherited from jax_default_matmul_precision: fp32
    operands get the full-precision passes (parity-grade), while bf16
    operands stay native — Mosaic rejects fp32 contract precision on
    bf16 inputs."""
    prec = (jax.lax.Precision.HIGHEST if a.dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    return lax.dot_general(a, b, (contract, ((), ())),
                           preferred_element_type=jnp.float32,
                           precision=prec)


def _keep_unit(seed0, seed1, bh, qpos, kpos):
    """Deterministic per-(batch·head, q-pos, k-pos) uniform in [0, 1).

    Counter-based murmur3-finalizer hash over plain int32 ops (multiply
    wraps two's-complement, xor, logical shifts) — the same code runs
    inside the Pallas kernels, under interpret mode, and as the dense
    test reference, so dropout masks are bitwise-identical across the
    forward, both backward passes, and the reference implementation.
    ``seed0``/``seed1`` carry 64 bits of seed (two int32 words — one
    word would collide by birthday bound across ~1e6 layer·step draws);
    ``bh`` scalar; ``qpos``/``kpos`` broadcastable int32 position
    arrays."""
    # numpy scalar constants inline as jaxpr literals — jnp constants
    # would become constvars, which pallas_call cannot lower
    h = (qpos * np.int32(-1640531527)                      # 2654435761
         ^ kpos * np.int32(-2048144777)                    # 2246822519
         ^ bh * np.int32(-1028477379)                      # 3266489917
         ^ seed0)
    h = h ^ lax.shift_right_logical(h, np.int32(16))
    h = h * np.int32(-2048144789)
    h = h ^ seed1
    h = h ^ lax.shift_right_logical(h, np.int32(16))
    h = h * np.int32(-1028477387)
    h = h ^ lax.shift_right_logical(h, np.int32(16))
    # 31 uniform bits -> [0, 1)
    bits = jnp.bitwise_and(h, np.int32(0x7FFFFFFF))
    return bits.astype(jnp.float32) * np.float32(1.0 / 2147483648.0)


def _block_for(T: int, window: Optional[int] = None) -> int:
    """Largest block in {512, 256, 128} that divides the lane-padded
    length — bounds zero-padding at 127 rows (a fixed 512 block would pad
    T=600 to 1024, wasting 41% of every MXU contraction).  A row of blocks
    under a causal ``window`` computes ``blk + window - 1`` keys for the
    ``window`` a query sees, so a window of up to two 512-blocks takes
    256-row blocks: 768 keys a row for a window of 512, not 1024, and 1280
    for a window of 1024, not 1536.  (On a v5e that pays only because
    ``_heads_per_step`` then puts 8 heads into a grid step, whose fixed
    cost a 256-block cannot carry alone; 128 loses to both: PERF.md
    section 6, PR 27.  At a window of 1024, 32 heads over 4 K/V heads x
    8192 x 128: 5.64 ms through the three kernels at 256 x 8 heads, 6.15
    at 512 x 4: PR 31.)"""
    Tp = -(-T // LANES) * LANES
    cap = 256 if window is not None and window <= 2 * _BLK else _BLK
    for blk in (_BLK, 256, LANES):
        if blk <= cap and Tp % blk == 0:
            return min(blk, Tp)
    return LANES


def _head_width(D: int) -> int:
    """The head size the kernels take of a head-major operand: ``D`` itself
    when it is under one lane tile or a whole number of them (a block's last
    dimension may equal the array's, so nothing is padded), else the next
    multiple.  (A token-major head is whole lane tiles; one a tile and a half
    wide, 192, comes as 128 and a ``_ROPE`` part and is never padded.)"""
    return D if D < LANES or D % LANES == 0 else -(-D // LANES) * LANES


def _heads_per_step(H: int, Dp: int, itemsize: int, masked: bool,
                    blk: int, rep: int = 1) -> int:
    """Heads of one batch entry a grid step takes.  They share the step's
    masks and its fixed cost, about 0.3 us on a v5e, a third of an
    interior forward pair of 512 x 512: 4 heads take 13 % off the three
    kernels at T = 8192, D = 128 (PERF.md section 6, PR 27).  Each head
    adds its operand blocks, accumulators and score tiles to the step's
    VMEM (compiled for a v5e at blk 512, D 128: 6 / 8 / 12 MiB for 1 / 2 /
    4 heads in bf16, 14 with dropout; 10 / 14 / 24 in fp32, of the 16 MiB
    a kernel may use), so: 4 for half-precision heads of one lane tile,
    2 when mask, segment or dropout operands come along, twice that at
    blocks of 256 or under, 1 otherwise.  Where ``rep`` query heads read
    one K/V head, a step's heads are of one group (a divisor of ``rep``),
    so that they share its K/V block; K, V, dK, dV and their accumulators
    are then the group's and not a head's, and half as many heads again
    fit: 6 of a group of 6 at blk 512 take 44.5 ms through the three
    kernels at 48 heads x 8192 x 128 where 3 take 47.3, 2 49.8 and the
    ungrouped 4 of the same call 45.1 (PERF.md section 6, PR 29)."""
    if Dp > LANES or itemsize > 2:
        return 1
    most = (2 if masked else 4) * (1 if blk > 256 else 2)
    if rep > 1:
        return max(hb for hb in range(1, min(8, most * 3 // 2) + 1)
                   if rep % hb == 0)
    return next(hb for hb in (8, 4, 2, 1) if hb <= most and H % hb == 0)


def _band_blocks(window: int, blk: int, n: int) -> int:
    """Blocks of ``blk`` keys that the queries of one block can see through
    a causal window of ``window`` keys (at most all ``n``): the diagonal
    block and those the lowest query row reaches back into."""
    return min(n, -(-(window - 1) // blk) + 1)


def fits_vmem(T: int, D: int, dropout: bool = False,
              segments: bool = False, window: Optional[int] = None,
              rope: int = 0) -> bool:
    """VMEM needed per grid step and head — independent of T now that K/V
    stream through the grid, at the block ``window`` selects (how many
    heads share a step is ``_heads_per_step``'s, from compiled sizes).
    ``D``: the value head's width, which q and k have too; ``rope``: what
    the score head has beyond it (0, or ``_ROPE``: q_rope, k_rope and dk's
    rope part then ride along, a lane tile each for two heads).
    Sized for the worst pass (backward dK/dV): six double-buffered operand
    blocks (q, k, v, do in; dk, dv out; a head under 128 lanes still fills
    a lane tile in VMEM), the double-buffered (8, blk) row tiles of lse
    and delta, two fp32 accumulator scratches, and the (blk, blk)
    score/prob/dp/ds intermediates.  Dropout holds two more live
    (blk, blk) tiles in the dk/dv pass (the hash tile u and p_acc
    alongside p/dp/ds); segments double-buffer the k-id (blk, LANES) and
    q-id (8, blk) tiles plus the (blk, blk) equality mask."""
    blk = _block_for(T, window)
    Dp = -(-D // LANES) * LANES
    operands = 6 * blk * Dp          # q, k, v, do, dk, dv blocks
    if rope:
        operands += 3 * blk * LANES  # q_rope, k_rope in; dk's rope part out
    stats = 2 * 8 * blk              # lse + delta row tiles
    resident = 2 * (operands + stats) * 4          # double-buffered
    scratch = 2 * blk * Dp * 4                     # dk/dv fp32 accumulators
    ntiles = 6 if dropout else 4     # s/p, dp, ds (+ u, p_acc)
    if segments:
        ntiles += 1                  # the id-equality mask
        resident += 2 * (blk * LANES + 8 * blk) * 4    # kseg + qseg tiles
    score = ntiles * blk * blk * 4
    return resident + scratch + score <= _VMEM_BUDGET


def _count(name: str, help: str, value: int = 1, **labels) -> None:
    from ..observability.metrics import get_registry
    c = get_registry().counter(name, help=help)
    (c.labels(**labels) if labels else c).inc(value)


def _pad_to(x, T, D):
    t, d = x.shape[-2:]
    if t == T and d == D:
        return x
    pad = [(0, 0)] * (x.ndim - 2) + [(0, T - t), (0, D - d)]
    return jnp.pad(x, pad)


def _unpad(x, T, D):
    return x if x.shape[-2:] == (T, D) else x[..., :T, :D]


# ---------------------------------------------------------------------------
# which array and which block a kernel's operand is
# ---------------------------------------------------------------------------

class _Layout:
    """The two forms the kernels take their operands in, and the block of
    each that a grid step holds.

    - head-major: q, do, o, dq as ``(B*H, T, D)`` folds and k, v, dk, dv as
      ``(B*Hkv, T, D)``; a step's ``hb`` heads are the leading ``hb`` of a
      ``(hb, blk, D)`` block.
    - token-major: ``(B, T, H*D)`` and ``(B, T, Hkv*D)``, what a projection
      writes; a step's heads are ``hb`` lane tiles of a ``(1, blk, hb*D)``
      block, its lane offset chosen by the index map, and a head inside
      the kernel is a static slice of ``D`` lanes (``D % 128 == 0``).

    ``rep = H // Hkv`` query heads read one K/V head: a step's heads are of
    one group (``hb`` divides ``rep``) and fetch that head's one block; with
    ``rep == 1`` the step holds ``hb`` K/V heads.  The grids of the forward
    and of dq run ``B*H/hb`` leading steps; dk/dv runs one leading step a
    K/V block, and the ``chunks = rep // hb`` steps of its query heads sit
    on the sequential axis, ``chunks`` of them a streamed block, adding
    into the one dk/dv accumulator.

    ``rope``: the score head has a trailing part of ``_ROPE`` numbers: q's
    as a block of ``hb * _ROPE`` lanes, the keys' as one head for all query
    heads, a (1, blk, LANES) block holding it twice.  Two heads share a lane
    tile of q's block, so a step then takes an even number of heads.

    Index maps and kernels address through this class alone; ``_Sweep``
    knows nothing of it."""

    def __init__(self, q, k, H: int, token_major: bool, masked: bool,
                 blk: int, rope: bool = False):
        """``q``, ``k``: the operands as the kernels get them (padded)."""
        if token_major:
            self.B, _, HD = q.shape
            self.D = HD // H
            self.Hkv = k.shape[2] // self.D
        else:
            BH, _, self.D = q.shape
            self.B = BH // H
            self.Hkv = k.shape[0] // self.B
        self.H, self.token_major = H, token_major
        self.rep = H // self.Hkv
        self.hb = hb = _heads_per_step(H, self.D, q.dtype.itemsize, masked,
                                       blk, self.rep)
        self.rope = rope
        if rope:        # float32 heads go one a step; a rope tile needs two
            self.hb = hb = max(hb, 2)
        self.hkv = hb if self.rep == 1 else 1      # K/V heads a step holds
        self.chunks = max(1, self.rep // hb)       # steps sharing a K/V block
        self.entry = H // hb                       # leading steps a batch entry

    def block(self, blk, kv=False):
        """Block shape of a q-like operand, or (``kv``) of a k-like one."""
        heads = self.hkv if kv else self.hb
        return ((1, blk, heads * self.D) if self.token_major
                else (heads, blk, self.D))

    def spec(self, blk, at, step, kv=False):
        """BlockSpec of a q-like or k-like operand.  ``at(g, t)``: the block
        along T; ``step(b, p)`` -> (leading step as the forward counts it,
        t).  A leading step's K/V block is that of its group."""
        c = self.chunks if kv else 1

        def index(b, g, p):
            b, t = step(b, p)
            if self.token_major:
                entry, heads = b // self.entry, b % self.entry
                return entry, at(g, t), heads if c == 1 else heads // c
            return b if c == 1 else b // c, at(g, t), 0
        return pl.BlockSpec(self.block(blk, kv), index)

    def rope_spec(self, blk, at, step, shared=False):
        """BlockSpec of a rope part: ``hb * _ROPE`` lanes of the step's
        heads, or (``shared``) the batch entry's one (blk, LANES) tile."""
        def index(b, g, p):
            b, t = step(b, p)
            return b // self.entry, at(g, t), 0 if shared else b % self.entry
        return pl.BlockSpec(
            (1, blk, LANES if shared else self.hb * _ROPE), index)

    def rope_tile(self, h, shared=False):
        """Index of the (blk, LANES) tile that holds head ``h``'s rope part
        in one of its halves, the lower for an even ``h``; ``shared``: of
        the one key head's tile, which holds it in both."""
        return (0, slice(None), pl.ds(0 if shared else h // 2 * LANES, LANES))

    def head(self, h):
        """Index of query head ``h``'s (blk, D) in a q-like block."""
        return ((0, slice(None), pl.ds(h * self.D, self.D))
                if self.token_major else h)

    def kv_head(self, h):
        """Index of the K/V head query head ``h`` reads, in a k-like
        block."""
        return self.head(h * self.hkv // self.hb)


# ---------------------------------------------------------------------------
# which block pairs a grid visits, and what each of them needs
# ---------------------------------------------------------------------------

class _Sweep:
    """The (rows, steps) grid of one kernel over the block pairs it must
    visit.  A row accumulates into one output block while ``steps``
    blocks of the other side stream past: k blocks past a q block
    (``streams="k"``: forward, dq) or q blocks past a k block
    (``streams="q"``: dk/dv).

    - no mask on position: ``n x n``, every pair.
    - causal: the triangle folded so that no step is dead.  Triangle row
      ``r`` has ``r + 1`` pairs; folded row ``g`` runs the long row
      ``n - 1 - g`` and then the short row that fills it up to
      ``n + 1`` steps (``n`` when ``n`` is odd, whose longest row stands
      alone).  The output block changes once inside a folded row, which
      Pallas writes back like any other change of block.
    - causal with a window: a row visits the ``_band_blocks`` blocks the
      band touches; those that would start before block 0 (or end after
      the last) are dead steps whose fetch is clamped onto the block
      already resident.

    ``at(g, t)`` works on traced scalars (kernels, index maps), Python
    ints (``analysis.pallas_lint``) and, with ``xp=np``, whole index
    grids (the block-pair counter)."""

    def __init__(self, n: int, blk: int, causal: bool,
                 window: Optional[int], streams: str, ragged: bool,
                 masked: bool):
        """``ragged``: the last k block holds padded keys.  ``masked``: a
        key-validity, segment-id or dropout operand comes along, so every
        pair takes the masked body."""
        self.n, self.blk, self.causal, self.window = n, blk, causal, window
        self.streams, self.ragged, self.masked = streams, ragged, masked
        if not causal:
            self.rows, self.steps = n, n
        elif window is None:
            self.even = 1 - n % 2
            self.rows, self.steps = (n + 1) // 2, n + self.even
        else:
            self.rows, self.steps = n, _band_blocks(window, blk, n)

    def at(self, g, t, xp=jnp):
        """(row block, streamed block, the block to fetch for it, live,
        first step of the row, last step of the row)."""
        n, k_streams = self.n, self.streams == "k"
        if not self.causal:
            return g, t, t, True, t == 0, t == n - 1
        if self.window is None:
            long = n - g
            in_long = t < long
            tri = xp.where(in_long, n - 1 - g, g - 1 + self.even)
            pos = xp.where(in_long, t, t - long)
            row = tri if k_streams else n - 1 - tri
            col = pos if k_streams else row + pos
            return row, col, col, True, pos == 0, pos == tri
        last = self.steps - 1
        if k_streams:
            col = g - last + t
            return g, col, xp.maximum(col, 0), col >= 0, t == 0, t == last
        col = g + t
        return g, col, xp.minimum(col, n - 1), col < n, t == 0, t == last

    def qk(self, g, t, xp=jnp):
        """``at`` with the pair named by side: (q block, k block, ...)."""
        row, col, _, live, first, last = self.at(g, t, xp)
        qi, kj = (row, col) if self.streams == "k" else (col, row)
        return qi, kj, live, first, last

    def interior(self, qi, kj):
        """Whether every (query, key) of the pair is visible, so that its
        scores need no mask: below the diagonal, inside the band, no
        padded key, no operand that masks.  A Python bool where the call
        decides it."""
        if self.masked:
            return False
        if self.window is not None and self.window < 2 * self.blk:
            return False        # a band under two blocks: no pair is whole
        ok = True
        if self.causal:
            ok = kj < qi
        if self.window is not None:
            ok = ok & ((qi - kj + 1) * self.blk <= self.window)
        if self.ragged:
            ok = ok & (kj != self.n - 1)
        return ok

    def count(self, heads: int) -> None:
        """Block pairs of one launch by kind, into the registry."""
        g, t = np.meshgrid(np.arange(self.rows), np.arange(self.steps),
                           indexing="ij")
        qi, kj, live, _, _ = self.qk(g, t, np)
        live = np.broadcast_to(live, g.shape)
        inner = live & np.broadcast_to(
            self.interior(qi, kj), g.shape)
        for kind, pairs in (("interior", inner), ("edge", live & ~inner),
                            ("dead", ~live)):
            _count("flash_block_pairs_total",
                   "block pairs the flash kernels' grids visit, per traced "
                   "launch and head: interior (no mask needed), edge "
                   "(masked in the kernel), dead (skipped; its fetch is "
                   "clamped onto the resident block)",
                   int(pairs.sum()) * heads, kind=kind)


def _both(a, b):
    """``a and b`` for Python bools and traced predicates alike."""
    if a is True:
        return b
    if b is True:
        return a
    if a is False or b is False:
        return False
    return jnp.logical_and(a, b)


def _run_pair(pair, live, interior):
    """Run ``pair(edge)`` once for a live block pair: the mask-free body
    for an interior pair, the masked one for an edge pair."""
    if interior is not False:
        pl.when(_both(live, interior))(lambda: pair(False))
    if interior is not True:
        not_interior = True if interior is False else jnp.logical_not(interior)
        pl.when(_both(live, not_interior))(lambda: pair(True))


def _tile_lanes(x, width: int):
    """A (rows, LANES) tile whose lanes are all equal, as (rows, width):
    whole copies of its vregs for a multiple of LANES, its leading lanes
    for a narrower head."""
    if width < LANES:
        return x[:, :width]
    reps = width // LANES
    return x if reps == 1 else jnp.tile(x, (1, reps))


def _to_columns(rows):
    """(8, blk) row tile (sublanes equal) → (blk, LANES) with lanes equal."""
    return jnp.broadcast_to(rows[:1, :], (LANES, rows.shape[1])).T


def _to_rows(columns):
    """(blk, LANES) with lanes equal → (8, blk) row tile."""
    return columns.T[:8, :]


def _visible(shape, qi, kj, blk, q_axis, *, causal, window, T_real, Tp,
             kvm, qseg, kseg):
    """Visibility of an edge pair's scores, ``None`` when nothing masks
    them, with the absolute positions the dropout hash keys on.
    ``q_axis``: the axis of ``shape`` the queries lie along; ``kvm`` and
    ``kseg`` broadcast along it, ``qseg`` along the other."""
    qpos = qi * blk + lax.broadcasted_iota(jnp.int32, shape, q_axis)
    kpos = kj * blk + lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    terms = []
    if T_real != Tp:
        terms.append(kpos < T_real)
    if causal:
        terms.append(qpos >= kpos)
    if window is not None:
        terms.append(kpos > qpos - window)
    if kvm is not None:
        terms.append(kvm > 0.5)
    if qseg is not None:
        # packed sequences: attend only within the same segment
        terms.append(qseg == kseg)
    valid = functools.reduce(jnp.logical_and, terms) if terms else None
    return valid, qpos, kpos


def _split_refs(refs, n_lead, has_mask, has_segments, dropout_rate):
    refs = list(refs)
    lead, refs = refs[:n_lead], refs[n_lead:]
    kvm_ref = refs.pop(0) if has_mask else None
    qseg_ref = refs.pop(0) if has_segments else None
    kseg_ref = refs.pop(0) if has_segments else None
    seed_ref = refs.pop(0) if dropout_rate else None
    return lead, kvm_ref, qseg_ref, kseg_ref, seed_ref, refs


def _row(ref):
    """The (1, blk) row of a sublane-broadcast (8, blk) tile."""
    return None if ref is None else ref[0][:1, :]


def _column(ref):
    """The (blk, 1) column of a lane-broadcast (blk, LANES) tile."""
    return None if ref is None else ref[0][:, :1]


def _rope_halves(kr_ref, lay, blk):
    """(``keys(h)``, ``half(h, x)``) of one block pair.  ``keys(h)``: the
    (blk, LANES) rope keys head ``h`` reads, the tile that holds them with
    the other half zeroed, so that a contraction over the tile's lanes is
    over the head's own ``_ROPE`` numbers; ``half(h, x)``: a (blk, LANES)
    ``x`` with all but head ``h``'s half zeroed."""
    lower = lax.broadcasted_iota(jnp.int32, (blk, LANES), 1) < _ROPE
    made = {}

    def half(h, x):
        return jnp.where(lower if h % 2 == 0 else ~lower, x,
                         jnp.zeros_like(x))

    def keys(h):        # the one key head's tile: two forms, even and odd
        if h % 2 not in made:
            made[h % 2] = half(h, kr_ref[lay.rope_tile(h, True)])
        return made[h % 2]

    return keys, half


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(*refs, scale, sweep, lay, has_mask, has_segments,
                dropout_rate, T_real, Tp):
    ((q_ref, k_ref, v_ref, *rope), kvm_ref, qseg_ref, kseg_ref, seed_ref,
     (o_ref, lse_ref, m_ref, l_ref, acc_ref)) = _split_refs(
        refs, 5 if lay.rope else 3, has_mask, has_segments, dropout_rate)
    hb, blk, D = lay.hb, sweep.blk, lay.D
    b = pl.program_id(0)
    i, j, live, first, last = sweep.qk(pl.program_id(1), pl.program_id(2))

    @pl.when(first)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, _NEG, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def pair(edge):
        valid = None
        if edge:
            # kvm / k ids: (1, blk) rows of sublane-broadcast tiles (k on
            # the lane axis, as s's columns); q ids: a (blk, 1) column
            valid, qpos, kpos = _visible(
                (blk, blk), i, j, blk, 0, causal=sweep.causal,
                window=sweep.window, T_real=T_real, Tp=Tp,
                kvm=_row(kvm_ref), qseg=_column(qseg_ref),
                kseg=_row(kseg_ref))
        if lay.rope:
            qr_ref, kr_ref = rope
            rope_keys, _ = _rope_halves(kr_ref, lay, blk)
        for h in range(hb):
            # m, l and alpha are (blk, LANES) tiles with equal lanes from
            # scratch to scratch: they meet the score tile as whole copies
            # of their vregs and the accumulator as they are
            q_h, kv_h = lay.head(h), lay.kv_head(h)
            v = v_ref[kv_h]
            s = _dot(q_ref[q_h], k_ref[kv_h], ((1,), (1,)))
            if lay.rope:
                s = s + _dot(qr_ref[lay.rope_tile(h)], rope_keys(h),
                             ((1,), (1,)))
            s = s * scale
            if valid is not None:
                s = jnp.where(valid, s, _NEG)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - _tile_lanes(m_new, blk))
            if valid is not None:
                # explicit zeroing: when a row is fully masked m_new ==
                # _NEG and exp(s - m_new) would be exp(0) = 1 on the
                # masked entries
                p = jnp.where(valid, p, 0.0)
            # the softmax normalizer uses the UNdropped probabilities;
            # only the value accumulation is dropped+rescaled
            # (FlashAttention's dropout placement — the mask is
            # regenerated bitwise in both backward passes from the same
            # counter hash)
            l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=-1, keepdims=True)
            m_ref[h] = m_new
            if dropout_rate:
                u = _keep_unit(seed_ref[0, 0], seed_ref[0, 1], b * hb + h,
                               qpos, kpos)
                p = jnp.where(u >= dropout_rate, p, 0.0) * (
                    1.0 / (1.0 - dropout_rate))
            pv = _dot(p.astype(v.dtype), v, ((1,), (0,)))
            acc_ref[q_h] = acc_ref[q_h] * _tile_lanes(alpha, D) + pv

    _run_pair(pair, live, sweep.interior(i, j))

    @pl.when(last)
    def _done():
        for h in range(hb):
            l = l_ref[h]
            l_safe = jnp.where(l == 0.0, 1.0, l)
            q_h = lay.head(h)
            o_ref[q_h] = (acc_ref[q_h] / _tile_lanes(l_safe, D)).astype(
                o_ref.dtype)
            lse_ref[h] = _to_rows(m_ref[h] + jnp.log(l_safe))


def _each_step(b, p):
    return b, p


def _dkv_step(lay):
    """(leading step, sequential step) of the dk/dv grid -> (the leading
    step as the forward counts it, the sweep's step): the ``chunks`` steps
    of a K/V group's query heads lie side by side on the sequential axis."""
    c = lay.chunks
    if c == 1:
        return _each_step
    return lambda b, p: (b * c + p % c, p // c)


def _specs(sweep, lay, step=_each_step):
    """BlockSpecs of one kernel's grid, by what a block follows: the
    grid's row or its streamed step; the ``hb`` heads of a step (``q``), the
    K/V heads they read (``kv``), or their batch entry's own tile."""
    blk = sweep.blk
    row = lambda g, t: sweep.at(g, t)[0]
    stream = lambda g, t: sweep.at(g, t)[2]

    def q(at):
        return lay.spec(blk, at, step)

    def kv(at):
        return lay.spec(blk, at, step, kv=True)

    def row_tile(at, per_head=True):        # (., 8, Tp) sublane-broadcast
        def index(b, g, p):
            b, t = step(b, p)
            return (b if per_head else b // lay.entry), 0, at(g, t)
        return pl.BlockSpec((lay.hb if per_head else 1, 8, blk), index)

    def column_tile(at):                    # (B, Tp, LANES) lane-broadcast
        def index(b, g, p):
            b, t = step(b, p)
            return b // lay.entry, at(g, t), 0
        return pl.BlockSpec((1, blk, LANES), index)

    return row, stream, q, kv, row_tile, column_tile


def _optional(sweep, specs, kvm, idq, idk, seed):
    """Specs and arrays of the operands that come only when asked for, in
    the order ``_split_refs`` takes them: key validity, q ids, k ids (all
    (B, Tp)), dropout seed.  What lies along a score tile's lanes goes as
    sublane-broadcast (B, 8, Tp) rows, what lies along its sublanes as
    lane-broadcast (B, Tp, LANES) columns: forward and dq stream the keys
    on the lanes and hold a row's queries on the sublanes, dk/dv the other
    way round."""
    row, stream, _, _, row_tile, column_tile = specs
    on_lanes = lambda x: (row_tile(stream, per_head=False), lax.broadcast_in_dim(
        x, (x.shape[0], 8, x.shape[1]), (0, 2)))
    on_sublanes = lambda x: (column_tile(row), lax.broadcast_in_dim(
        x, (*x.shape, LANES), (0, 1)))
    key, query = ((on_lanes, on_sublanes) if sweep.streams == "k"
                  else (on_sublanes, on_lanes))
    found = []
    if kvm is not None:
        found.append(key(kvm))
    if idq is not None:
        found += [query(idq), key(idk)]
    if seed is not None:
        found.append((pl.BlockSpec(memory_space=pltpu.SMEM), seed))
    return [spec for spec, _ in found], [x for _, x in found]


_SEM = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _rope_operands(qr, kr, Tp):
    """[q_rope, k_rope] as the kernels take them, or [] where there are none:
    T a whole number of blocks, and the one key head twice, a lane tile whose
    lower half the even heads read and whose upper half the odd."""
    if kr is None:
        return []
    qr, kr = (_pad_to(x, Tp, x.shape[-1]) for x in (qr, kr))
    return [qr, jnp.concatenate([kr, kr], -1)]


def _rope_heads_sum(parts):
    """The one key head's gradient from the dk/dv kernel's float32 partial
    sums, (B, a batch entry's leading steps, Tp, a tile's halves, _ROPE): a
    step's even heads added up in the lower half and its odd ones in the
    upper -> (B, Tp, _ROPE), the sum over every query head."""
    return parts.sum((1, 3))


def _padded(x, Tp, token_major):
    """An operand as the kernels take it: T a whole number of blocks, a
    head-major head of the width ``_head_width`` names."""
    last = x.shape[-1]
    return _pad_to(x, Tp, last if token_major else _head_width(last))


@functools.partial(jax.jit, static_argnames=("scale", "causal", "H",
                                             "dropout_rate", "window",
                                             "token_major"))
def _fwd(q, k, v, kvm, idq, idk, seed, scale, causal, H, dropout_rate,
         window=None, token_major=False, qr=None, kr=None):
    """q, k, v: head-major (B*H, T, D), (B*Hkv, T, D) or token-major
    (B, T, H*D), (B, T, Hkv*D).  kvm: (B, Tp) fp32 key validity or None.
    idq/idk: (B, Tp) int32 segment ids as the query and the key side see
    them, or None.  seed: (1, 2) int32 dropout seed or None.  qr, kr: the
    score head's rope part, (B, T, H*_ROPE) and (B, T, _ROPE), or None.  Returns o in q's form and the logsumexp as
    (B*H, 8, Tp) sublane-broadcast row tiles."""
    T = q.shape[1]
    blk = _block_for(T, window)
    Tp = -(-T // blk) * blk
    qp, kp, vp = (_padded(x, Tp, token_major) for x in (q, k, v))
    masked = kvm is not None or idq is not None or seed is not None
    ropes = _rope_operands(qr, kr, Tp)
    lay = _Layout(qp, kp, H, token_major, masked, blk, bool(ropes))
    hb, BH = lay.hb, lay.B * H
    sweep = _Sweep(Tp // blk, blk, causal, window, "k", Tp != T, masked)
    specs = row, stream, q_like, kv_like, row_tile, _ = _specs(sweep, lay)
    more_specs, more = _optional(sweep, specs, kvm, idq, idk, seed)
    if ropes:
        more_specs = [lay.rope_spec(blk, row, _each_step),
                      lay.rope_spec(blk, stream, _each_step, True),
                      *more_specs]
        more = [*ropes, *more]
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, sweep=sweep, lay=lay,
                          has_mask=kvm is not None,
                          has_segments=idq is not None,
                          dropout_rate=dropout_rate, T_real=T, Tp=Tp),
        grid=(BH // hb, sweep.rows, sweep.steps),
        in_specs=[q_like(row), kv_like(stream), kv_like(stream),
                  *more_specs],
        out_specs=[q_like(row), row_tile(row)],
        out_shape=[jax.ShapeDtypeStruct(qp.shape, q.dtype),
                   jax.ShapeDtypeStruct((BH, 8, Tp), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hb, blk, LANES), jnp.float32),
                        pltpu.VMEM((hb, blk, LANES), jnp.float32),
                        pltpu.VMEM(lay.block(blk), jnp.float32)],
        compiler_params=_SEM,
        interpret=interpret(),
        name="flash_fwd",
    )(qp, kp, vp, *more)
    return _unpad(o, T, q.shape[-1]), lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _dq_kernel(*refs, scale, sweep, lay, has_mask, has_segments,
               dropout_rate, T_real, Tp):
    ((q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rope), kvm_ref,
     qseg_ref, kseg_ref, seed_ref, out) = _split_refs(
        refs, 8 if lay.rope else 6, has_mask, has_segments, dropout_rate)
    if lay.rope:        # dq's rope part beside dq, and its accumulator last
        (qr_ref, kr_ref), (dq_ref, dqr_ref, dq_acc, lse_w, delta_w,
                           dqr_acc) = rope, out
    else:
        dq_ref, dq_acc, lse_w, delta_w = out
    hb, blk = lay.hb, sweep.blk
    b = pl.program_id(0)
    i, j, live, first, last = sweep.qk(pl.program_id(1), pl.program_id(2))

    @pl.when(first)
    def _init():
        dq_acc[...] = jnp.zeros(dq_acc.shape, jnp.float32)
        if lay.rope:
            dqr_acc[...] = jnp.zeros(dqr_acc.shape, jnp.float32)
        # the row's statistics arrive as (8, blk) row tiles and are
        # widened once a row to the equal-lane columns the pairs use
        for h in range(hb):
            lse_w[h] = _to_columns(lse_ref[h])
            delta_w[h] = _to_columns(delta_ref[h])

    def pair(edge):
        valid = None
        if edge:
            valid, qpos, kpos = _visible(
                (blk, blk), i, j, blk, 0, causal=sweep.causal,
                window=sweep.window, T_real=T_real, Tp=Tp,
                kvm=_row(kvm_ref), qseg=_column(qseg_ref),
                kseg=_row(kseg_ref))
        if lay.rope:
            rope_keys, _ = _rope_halves(kr_ref, lay, blk)
        for h in range(hb):
            q_h, kv_h = lay.head(h), lay.kv_head(h)
            k = k_ref[kv_h]
            s = _dot(q_ref[q_h], k, ((1,), (1,)))
            if lay.rope:
                s = s + _dot(qr_ref[lay.rope_tile(h)], rope_keys(h),
                             ((1,), (1,)))
            s = s * scale
            p = jnp.exp(s - _tile_lanes(lse_w[h], blk))
            if valid is not None:
                p = jnp.where(valid, p, 0.0)
            dp = _dot(do_ref[q_h], v_ref[kv_h], ((1,), (1,)))
            if dropout_rate:
                # dS = P ∘ (M ∘ (dO Vᵀ)/keep − delta): same counter hash
                # as the forward, so the mask is bitwise-identical
                u = _keep_unit(seed_ref[0, 0], seed_ref[0, 1], b * hb + h,
                               qpos, kpos)
                dp = jnp.where(u >= dropout_rate, dp, 0.0) * (
                    1.0 / (1.0 - dropout_rate))
            ds = (p * (dp - _tile_lanes(delta_w[h], blk))).astype(k.dtype)
            dq_acc[q_h] += _dot(ds, k, ((1,), (0,)))
            if lay.rope:    # lands in the head's half: the other is zeros
                dqr_acc[lay.rope_tile(h)] += _dot(ds, rope_keys(h),
                                                  ((1,), (0,)))

    _run_pair(pair, live, sweep.interior(i, j))

    @pl.when(last)
    def _done():
        dq_ref[...] = (dq_acc[...] * scale).astype(dq_ref.dtype)
        if lay.rope:
            dqr_ref[...] = (dqr_acc[...] * scale).astype(dqr_ref.dtype)


def _dkv_kernel(*refs, scale, sweep, lay, has_mask, has_segments,
                dropout_rate, T_real, Tp):
    """In transposed form: the score tile is k-major, (k rows, q columns),
    so p and ds are born as the left operands dV = Pᵀ dO and dK = dSᵀ Q
    want, and a query's statistics are (1, blk) rows that broadcast along
    sublanes.  The query heads of a K/V group add into one accumulator in
    fp32: ``lay.chunks`` steps a streamed block, ``hb`` heads each."""
    ((q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rope), kvm_ref,
     qseg_ref, kseg_ref, seed_ref, out) = _split_refs(
        refs, 8 if lay.rope else 6, has_mask, has_segments, dropout_rate)
    if lay.rope:        # dk's rope part beside dk and dv
        (qr_ref, kr_ref), (dk_ref, dv_ref, dkr_ref, dk_acc, dv_acc,
                           dkr_acc) = rope, out
    else:
        dk_ref, dv_ref, dk_acc, dv_acc = out
    hb, blk = lay.hb, sweep.blk
    b, g, p = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    b, t = _dkv_step(lay)(b, p)
    j, i, live, first, last = sweep.qk(g, t)
    if lay.chunks > 1:
        chunk = p % lay.chunks
        first = jnp.logical_and(first, chunk == 0)
        last = jnp.logical_and(last, chunk == lay.chunks - 1)

    @pl.when(first)
    def _init():
        dk_acc[...] = jnp.zeros(dk_acc.shape, jnp.float32)
        dv_acc[...] = jnp.zeros(dv_acc.shape, jnp.float32)
        if lay.rope:
            dkr_acc[...] = jnp.zeros(dkr_acc.shape, jnp.float32)

    def pair(edge):
        valid = None
        if edge:
            # absolute (qpos, kpos) arguments match the fwd/dq passes
            # exactly, so the regenerated dropout mask is bitwise-identical
            valid, qpos, kpos = _visible(
                (blk, blk), j, i, blk, 1, causal=sweep.causal,
                window=sweep.window, T_real=T_real, Tp=Tp,
                kvm=_column(kvm_ref), qseg=_row(qseg_ref),
                kseg=_column(kseg_ref))
        if lay.rope:
            rope_keys, half = _rope_halves(kr_ref, lay, blk)
        for h in range(hb):
            q_h, kv_h = lay.head(h), lay.kv_head(h)
            q = q_ref[q_h]
            do = do_ref[q_h]
            s = _dot(k_ref[kv_h], q, ((1,), (1,)))             # (bk, bq)
            if lay.rope:
                qr = qr_ref[lay.rope_tile(h)]
                s = s + _dot(rope_keys(h), qr, ((1,), (1,)))
            s = s * scale
            # padded q rows contribute nothing: their do rows are zero
            p = jnp.exp(s - lse_ref[h][:1, :])
            if valid is not None:
                p = jnp.where(valid, p, 0.0)
            dp = _dot(v_ref[kv_h], do, ((1,), (1,)))
            p_acc = p
            if dropout_rate:
                u = _keep_unit(seed_ref[0, 0], seed_ref[0, 1], b * hb + h,
                               qpos, kpos)
                keep = u >= dropout_rate
                inv_keep = 1.0 / (1.0 - dropout_rate)
                p_acc = jnp.where(keep, p, 0.0) * inv_keep
                dp = jnp.where(keep, dp, 0.0) * inv_keep
            dv_acc[kv_h] += _dot(p_acc.astype(do.dtype), do, ((1,), (0,)))
            ds = (p * (dp - delta_ref[h][:1, :])).astype(q.dtype)
            dk_acc[kv_h] += _dot(ds, q, ((1,), (0,)))
            if lay.rope:
                # the head's half of ds^T [q_rope of two heads]; the one
                # key head's tile takes every head of the step, the even
                # ones in its lower half
                dkr_acc[lay.rope_tile(h, True)] += half(
                    h, _dot(ds, qr, ((1,), (0,))))

    _run_pair(pair, live, sweep.interior(j, i))

    @pl.when(last)
    def _done():
        dk_ref[...] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)
        if lay.rope:
            dkr_ref[...] = (dkr_acc[...] * scale).astype(dkr_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "causal", "H",
                                             "dropout_rate", "window",
                                             "token_major"))
def _bwd(q, k, v, o, lse, do, kvm, idq, idk, seed, scale, causal, H,
         dropout_rate, window=None, token_major=False, qr=None, kr=None):
    """lse: the forward's (B*H, 8, Tp) row tiles; the rest as in _fwd.
    dk and dv come back in k's form: one head a K/V head, the sum over its
    query heads taken in the kernel's fp32 accumulator.  With a rope part
    also dq_rope and dk_rope in qr's and kr's forms: the one shared head's
    as the sum over all query heads, a grid step's heads in the kernel's
    accumulator and the steps of a batch entry here, in float32."""
    T = q.shape[1]
    blk = _block_for(T, window)
    Tp = -(-T // blk) * blk
    qp, kp, vp, dop = (_padded(x, Tp, token_major) for x in (q, k, v, do))
    masked = kvm is not None or idq is not None or seed is not None
    ropes = _rope_operands(qr, kr, Tp)
    lay = _Layout(qp, kp, H, token_major, masked, blk, bool(ropes))
    hb, BH = lay.hb, lay.B * H
    prod = do.astype(jnp.float32) * o.astype(jnp.float32)
    if token_major:         # (B, T, H*D) -> a row of T a head
        delta = jnp.sum(prod.reshape(*token_tile_axes(lay.B, T), H, -1), -1)
        delta = jnp.moveaxis(delta, 3, 1).reshape(BH, T)
    else:
        delta = jnp.sum(prod, -1)
    if Tp != T:
        delta = jnp.pad(delta, ((0, 0), (0, Tp - T)))
    delta = lax.broadcast_in_dim(delta, (BH, 8, Tp), (0, 2))
    kinds = dict(scale=scale, lay=lay, has_mask=kvm is not None,
                 has_segments=idq is not None, dropout_rate=dropout_rate,
                 T_real=T, Tp=Tp)
    acc = lambda *shape: pltpu.VMEM(shape, jnp.float32)

    # dq: a row is a q block (with do and its statistics); K, V and the
    # key-side tiles stream
    sweep = _Sweep(Tp // blk, blk, causal, window, "k", Tp != T, masked)
    specs = row, stream, q_like, kv_like, row_tile, _ = _specs(sweep, lay)
    more_specs, more = _optional(sweep, specs, kvm, idq, idk, seed)
    out_specs, out_shape = q_like(row), jax.ShapeDtypeStruct(qp.shape, q.dtype)
    scratch = [acc(*lay.block(blk)), acc(hb, blk, LANES), acc(hb, blk, LANES)]
    if ropes:
        q_rope = lay.rope_spec(blk, row, _each_step)
        more_specs = [q_rope, lay.rope_spec(blk, stream, _each_step, True),
                      *more_specs]
        more = [*ropes, *more]
        out_specs = [out_specs, q_rope]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct(ropes[0].shape, qr.dtype)]
        scratch.append(acc(1, blk, hb * _ROPE))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, sweep=sweep, **kinds),
        grid=(BH // hb, sweep.rows, sweep.steps),
        in_specs=[q_like(row), kv_like(stream), kv_like(stream),
                  q_like(row), row_tile(row), row_tile(row), *more_specs],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=_SEM,
        interpret=interpret(),
        name="flash_dq",
    )(qp, kp, vp, dop, lse, delta, *more)
    dqr = None
    if ropes:
        dq, dqr = dq
        dqr = _unpad(dqr, T, qr.shape[-1])

    # dk/dv: a row is a k block (with v and the key-side tiles); Q, dO,
    # their statistics and the q ids stream, a K/V group's query heads
    # ``lay.chunks`` steps a block
    sweep = _Sweep(Tp // blk, blk, causal, window, "q", Tp != T, masked)
    specs = row, stream, q_like, kv_like, row_tile, _ = _specs(
        sweep, lay, _dkv_step(lay))
    more_specs, more = _optional(sweep, specs, kvm, idq, idk, seed)
    out_specs = [kv_like(row), kv_like(row)]
    out_shape = [jax.ShapeDtypeStruct(kp.shape, k.dtype),
                 jax.ShapeDtypeStruct(kp.shape, v.dtype)]
    scratch = [acc(*lay.block(blk, kv=True))] * 2
    if ropes:
        more_specs = [lay.rope_spec(blk, stream, _each_step),
                      lay.rope_spec(blk, row, _each_step, True), *more_specs]
        more = [*ropes, *more]
        # a float32 partial sum a leading step (its hb heads)
        out_specs.append(pl.BlockSpec(
            (1, blk, LANES), lambda b, g, p: (b, row(g, p), 0)))
        out_shape.append(jax.ShapeDtypeStruct((BH // hb, Tp, LANES),
                                              jnp.float32))
        scratch = [*scratch, acc(1, blk, LANES)]
    dk, dv, *dkr = pl.pallas_call(
        functools.partial(_dkv_kernel, sweep=sweep, **kinds),
        grid=(BH // hb // lay.chunks, sweep.rows, sweep.steps * lay.chunks),
        in_specs=[q_like(stream), kv_like(row), kv_like(row),
                  q_like(stream), row_tile(stream), row_tile(stream),
                  *more_specs],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=_SEM,
        interpret=interpret(),
        name="flash_dkv",
    )(qp, kp, vp, dop, lse, delta, *more)
    if ropes:
        dkr = _unpad(_rope_heads_sum(dkr[0].reshape(
            lay.B, lay.entry, Tp, 2, _ROPE)).astype(kr.dtype), T, _ROPE)
    return (_unpad(dq, T, q.shape[-1]), _unpad(dk, T, k.shape[-1]),
            _unpad(dv, T, k.shape[-1]), dqr, dkr if ropes else None)


# ---------------------------------------------------------------------------
# public op
# ---------------------------------------------------------------------------

def _count_call(q, k, H, token_major, causal, window, masked, launches,
                kr=None):
    """Trace-time counters of one flash call (docs/observability.md): the
    form its operands took (``rope="shared"`` where the score head has a
    rope part: one key head for all query heads),
    the block pairs each launch's grid visits by kind, and the operands
    padded and results sliced around the kernels (forward: q, k, v in and o
    out; backward: q, k, v, do in and dq, dk, dv out; the rope parts too)."""
    T, last = q.shape[1:]
    blk = _block_for(T, window)
    Tp = -(-T // blk) * blk
    grouped = q.size != k.size
    _count("flash_calls_total",
           "flash-attention calls traced (a forward, or a backward), by the "
           "form of their operands: token_major (B, T, H*D) or head_major "
           "(B*H, T, D); K/V once per K/V head (grouped) or per query head",
           layout="token_major" if token_major else "head_major",
           kv="grouped" if grouped else "per_query_head",
           **({} if kr is None else {"rope": "shared"}))
    heads = q.shape[0] * (H if token_major else 1)      # B * H
    for streams in launches:
        _Sweep(Tp // blk, blk, causal, window, streams, Tp != T,
               masked).count(heads)
    fits = Tp == T and (token_major or _head_width(last) == last)
    _count("flash_pad_copies_total",
           "flash-attention operands padded and results sliced, per "
           "traced call; 0 when T is a whole number of blocks and D is "
           "under 128 or a multiple of it",
           0 if fits else ({"k": 4, "kq": 7}[launches]
                           + (0 if kr is None else 2 * len(launches))))


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10, 11, 12))
def _flash(q, k, v, kvm, idq, idk, seed, scale: float, causal: bool,
           H: int, dropout_rate: float, window: Optional[int],
           token_major: bool, qr=None, kr=None):
    return _flash_fwd(q, k, v, kvm, idq, idk, seed, scale, causal, H,
                      dropout_rate, window, token_major, qr, kr)[0]


def _flash_fwd(q, k, v, kvm, idq, idk, seed, scale, causal, H,
               dropout_rate, window, token_major, qr=None, kr=None):
    masked = kvm is not None or idq is not None or seed is not None
    _count_call(q, k, H, token_major, causal, window, masked, "k", kr)
    o, lse = _fwd(q, k, v, kvm, idq, idk, seed, scale, causal, H,
                  dropout_rate, window, token_major, qr, kr)
    # named before they part into primal output and residuals: a name on
    # the output alone leaves the residual o un-named one equation
    # upstream, and partial evaluation replays the kernel to get it
    o = checkpoint_name(o, FLASH_OUT_NAME)
    lse = checkpoint_name(lse, FLASH_LSE_NAME)
    return o, (q, k, v, o, lse, kvm, idq, idk, seed, qr, kr)


def _flash_bwd(scale, causal, H, dropout_rate, window, token_major, res,
               do):
    q, k, v, o, lse, kvm, idq, idk, seed, qr, kr = res
    masked = kvm is not None or idq is not None or seed is not None
    _count_call(q, k, H, token_major, causal, window, masked, "kq", kr)
    dq, dk, dv, dqr, dkr = _bwd(q, k, v, o, lse, do, kvm, idq, idk, seed,
                                scale, causal, H, dropout_rate, window,
                                token_major, qr, kr)
    dkvm = None if kvm is None else jnp.zeros_like(kvm)
    # int primals -> float0 cotangents
    f0 = lambda a: (None if a is None
                    else np.zeros(a.shape, jax.dtypes.float0))
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            dkvm, f0(idq), f0(idk), f0(seed), dqr, dkr)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _attend(q, k, v, token_major, causal, scale, kv_mask, dropout_rate,
            dropout_seed, segment_ids, window, q_rope=None, k_rope=None):
    """The checks and operand preparation both entries share.  q, k, v
    arrive 4-D, heads on axis 1 (head-major) or 2 (token-major); the rope
    part of a wider score head (token-major only) as (B, T, H, _ROPE) and
    (B, T, 1, _ROPE)."""
    h_axis, t_axis = (2, 1) if token_major else (1, 2)
    if q.ndim != 4:
        raise ValueError("expected "
                         f"{'(B, T, H, D)' if token_major else '(B, H, T, D)'}"
                         f", got {q.shape}")
    B, T, D = q.shape[0], q.shape[t_axis], q.shape[3]
    H, Hkv = q.shape[h_axis], k.shape[h_axis]
    grouped = list(q.shape)
    grouped[h_axis] = Hkv
    if k.shape != v.shape or list(k.shape) != grouped or H % Hkv:
        raise ValueError(
            "flash_attention requires k and v of q's shape, or of a number "
            f"of heads that divides q's: got {q.shape}, {k.shape}, "
            f"{v.shape}")
    if token_major and D % LANES:
        raise ValueError("token-major operands need a head of whole lane "
                         f"tiles (D % {LANES} == 0; a score head of a tile "
                         f"and a half comes as q_rope and k_rope beside "
                         f"it), got D = {D}")
    if (q_rope is None) != (k_rope is None):
        raise ValueError("q_rope and k_rope come together")
    if q_rope is not None and (
            q_rope.shape != (B, T, H, _ROPE) or Hkv != H or H % 2
            or k_rope.shape != (B, T, 1, _ROPE)):
        raise ValueError(
            f"a score head's rope part is q_rope (B, T, H, {_ROPE}) and "
            f"k_rope (B, T, 1, {_ROPE}) beside token-major q, k, v at "
            f"one, even head count: got {q.shape}, {k.shape}, "
            f"{q_rope.shape}, {k_rope.shape}")
    dropout_rate = float(dropout_rate)
    if not 0.0 <= dropout_rate < 1.0:
        raise ValueError(f"dropout_rate must be in [0, 1), got "
                         f"{dropout_rate}")
    if dropout_rate and dropout_seed is None:
        raise ValueError("dropout_rate > 0 requires dropout_seed")
    if window is not None:
        window = int(window)
        if not causal or window < 1:
            raise ValueError("window needs causal=True and window >= 1, "
                             f"got causal={causal}, window={window}")
    if scale is None:       # over the whole score head
        scale = 1.0 / math.sqrt(D + (0 if q_rope is None else _ROPE))
    blk = _block_for(T, window)
    Tp = -(-T // blk) * blk
    kvm = None
    if kv_mask is not None:
        if kv_mask.shape != (B, T):
            raise ValueError(f"kv_mask must be (B, T) = {(B, T)}, got "
                             f"{kv_mask.shape}")
        kvm = jnp.pad(kv_mask.astype(jnp.float32), ((0, 0), (0, Tp - T)))
    seed = None
    if dropout_rate:
        s = jnp.asarray(dropout_seed, jnp.int32).reshape(-1)
        if s.size == 1:
            # single-word seeds get a derived second word (no extra
            # entropy, but the kernel contract is two words)
            s = jnp.stack([s[0], s[0] ^ np.int32(0x5555AAAA)])
        elif s.size != 2:
            raise ValueError("dropout_seed must be 1 or 2 int32 words, "
                             f"got {s.size}")
        seed = s.reshape(1, 2)
    idq = idk = None
    if segment_ids is not None:
        if segment_ids.shape != (B, T):
            raise ValueError(f"segment_ids must be (B, T) = {(B, T)}, "
                             f"got {segment_ids.shape}")
        # padded positions get id -1 on the q side and -2 on the k side,
        # so padding never matches anything (incl. other padding)
        ids = segment_ids.astype(jnp.int32)
        idq = jnp.pad(ids, ((0, 0), (0, Tp - T)), constant_values=-1)
        idk = jnp.pad(ids, ((0, 0), (0, Tp - T)), constant_values=-2)
    if token_major:         # the projection's own output: a free reshape
        fold = lambda x: x.reshape(B, T, -1)
    else:
        fold = lambda x: x.reshape(-1, T, D)
    ropes = () if q_rope is None else (fold(q_rope), fold(k_rope))
    out = _flash(fold(q), fold(k), fold(v), kvm, idq, idk, seed,
                 float(scale), bool(causal), H, dropout_rate, window,
                 token_major, *ropes)
    return out.reshape(q.shape)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = False,
                    scale: Optional[float] = None,
                    kv_mask: Optional[jax.Array] = None,
                    dropout_rate: float = 0.0,
                    dropout_seed: Optional[jax.Array] = None,
                    segment_ids: Optional[jax.Array] = None,
                    window: Optional[int] = None) -> jax.Array:
    """softmax(q k^T * scale [+ causal mask]) v without materializing the
    score matrix in HBM.  Head-major self-attention operands (equal
    sequence lengths): q (B, H, T, D); k, v (B, Hkv, T, D) with ``Hkv``
    dividing ``H`` — query head h reads K/V head ``h // (H // Hkv)``, and
    dk, dv come back at ``Hkv`` heads.  K/V are streamed through VMEM in
    blocks, so the sequence length is bounded by HBM, not VMEM.

    ``kv_mask``: optional (B, T) bool key-validity (True = attend) — the
    key-padding mask of BERT-style batches, streamed alongside the K/V
    blocks as sublane-broadcast (B, 8, T) tiles (the upstream
    jax.experimental flash kernel's SegmentIds layout).  Composes with
    ``causal``.  Queries whose keys are ALL masked produce zero output
    rows (the dense softmax would give a uniform average instead).

    ``dropout_rate`` + ``dropout_seed`` (int32 scalar, e.g. drawn per
    step from a PRNGKey): attention-probability dropout computed INSIDE
    the kernel from a counter-based hash of the absolute positions —
    no (T, T) mask materializes, and the backward passes regenerate the
    identical mask from the same counters (FlashAttention's dropout
    placement: the softmax normalizer is undropped, the value
    accumulation is dropped and rescaled by 1/keep).

    ``segment_ids``: optional (B, T) int32 for packed sequences —
    position pairs attend only within equal ids (q-ids stream as
    lane-broadcast tiles, k-ids as sublane tiles).  Composes with
    ``causal``/``kv_mask``/dropout.  Rows whose segment has no other
    member still see themselves (the diagonal id always matches).

    ``window``: a static sliding window on top of ``causal=True`` — key j
    is visible to query i iff ``i - window < j <= i``.  Each of the three
    kernels then visits only the block pairs the band touches; with
    ``window=None`` they are the programs they are without it."""
    return _attend(q, k, v, False, causal, scale, kv_mask, dropout_rate,
                   dropout_seed, segment_ids, window)


def flash_attention_token_major(q: jax.Array, k: jax.Array, v: jax.Array,
                                causal: bool = False,
                                scale: Optional[float] = None,
                                kv_mask: Optional[jax.Array] = None,
                                dropout_rate: float = 0.0,
                                dropout_seed: Optional[jax.Array] = None,
                                segment_ids: Optional[jax.Array] = None,
                                window: Optional[int] = None,
                                q_rope: Optional[jax.Array] = None,
                                k_rope: Optional[jax.Array] = None
                                ) -> jax.Array:
    """``flash_attention`` on operands where the projections wrote them:
    q (B, T, H, D); k, v (B, T, Hkv, D); the result (B, T, H, D).  The
    kernels read them as (B, T, H*D) — no axis is moved and no K/V head
    repeated on the way in or out, forward or backward.  Needs
    ``D % 128 == 0`` (a head is then whole lane tiles of a token's row);
    everything else as in ``flash_attention``, the same three kernels.

    ``q_rope`` (B, T, H, 64) with ``k_rope`` (B, T, 1, 64): the trailing part of a score head wider than the value head (latent
    attention: 192 = 128 + 64 against values of 128), each part where its
    projection wrote it.  A score is ``q . k + q_rope . k_rope`` over
    ``sqrt(D + 64)`` unless ``scale`` says otherwise; one ``k_rope`` head
    serves every query head and takes the sum of their gradients.  K/V at
    q's head count, an even one."""
    return _attend(q, k, v, True, causal, scale, kv_mask, dropout_rate,
                   dropout_seed, segment_ids, window, q_rope, k_rope)
