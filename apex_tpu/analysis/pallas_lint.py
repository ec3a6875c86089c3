"""Static precondition lint over the Pallas kernel family.

The TPU kernels in :mod:`apex_tpu.ops` all follow the same discipline:
pad the operand to a whole number of ``(BLOCK_ROWS, LANES)`` tiles,
launch a 1-D (or small N-D) grid over them, and alias the in-place
operands onto their outputs.  Every one of those conventions has a
silent failure mode — a block shape that does not divide the padded
operand reads garbage rows, an index map that steps past the last
block writes out of bounds (interpret mode masks this; hardware does
not), and a double-aliased output is two kernels racing one buffer.

This module checks the conventions *statically*: it intercepts
``pl.pallas_call`` while tracing each kernel's public wrapper on tiny
operands, records every call's grid/specs/aliases as a
:class:`KernelSite`, and lints the sites without ever executing the
kernel on hardware.  It is the net under ROADMAP item 1a's
paged-attention kernel — that kernel will be the first one written
against these checks (tests/test_pallas_lint.py runs them tier-1).

Checks per site:

- **block divisibility**: every blocked operand's (padded) shape must
  divide by its ``BlockSpec`` block shape — the kernels pre-pad via
  ``to_2d``/``_pad2`` exactly so this holds, and a refactor that drops
  the pad reads partial tiles; a dim that does not divide is a fault
  where a grid step reaches its partial block (an operand read by column
  blocks out of a wider array, the short convolution's, never does);
- **index-map bounds**: the block index the spec's ``index_map``
  returns at every grid corner must stay within
  ``[0, shape[d] // block[d])`` for every dim; a map that reads
  scalar-prefetch operands (the grouped products' work items) is
  evaluated with the values the launch was traced with, at every point
  of its grid, and the driver traces such a launch once per
  representative set of values;
- **aliasing declared exactly once**: ``input_output_aliases`` maps
  distinct inputs to distinct outputs, indices in range, and the
  aliased pair agrees on shape + dtype (donating a buffer of the
  wrong size is a lowering error on TPU and silent corruption in
  interpret mode).
"""

from __future__ import annotations

import contextlib
import functools
import itertools
from dataclasses import dataclass, field
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

__all__ = ["KernelSite", "capture_kernel_sites", "check_site",
           "collect_kernel_sites", "lint_pallas_kernels"]


@dataclass
class KernelSite:
    """One recorded ``pl.pallas_call`` launch: the static spec plus the
    operand shapes it was invoked with."""
    name: str
    grid: Tuple[int, ...]
    in_specs: List[Any]
    out_specs: List[Any]
    in_shapes: List[Tuple[Tuple[int, ...], str]]
    out_shapes: List[Tuple[Tuple[int, ...], str]]
    input_output_aliases: Dict[int, int] = field(default_factory=dict)
    # a ``PrefetchScalarGridSpec`` launch: the values of its scalar
    # operands, which its index maps take after the grid indices; None
    # where the launch was traced with abstract ones
    scalar_prefetch: Optional[List[Any]] = field(default_factory=list)

    def describe(self) -> str:
        return (f"{self.name}: grid={self.grid}, "
                f"{len(self.in_shapes)} in / {len(self.out_shapes)} out, "
                f"aliases={dict(self.input_output_aliases)}")


def _as_seq(x) -> List[Any]:
    if x is None:
        return []
    if isinstance(x, (list, tuple)):
        return list(x)
    return [x]


def _kernel_name(fn) -> str:
    while isinstance(fn, functools.partial):
        fn = fn.func
    return getattr(fn, "__name__", repr(fn))


@contextlib.contextmanager
def capture_kernel_sites(into: List[KernelSite]) -> Iterator[None]:
    """Patch ``pallas.pallas_call`` so every launch traced inside the
    context appends a :class:`KernelSite` to ``into``, then delegates
    to the real implementation.  The ops modules all bind the *module*
    (``from jax.experimental import pallas as pl``), so one patch
    covers every kernel file.  Callers must clear the jitted wrappers'
    trace caches first or a warm cache skips the trace entirely —
    :func:`collect_kernel_sites` does both."""
    from jax.experimental import pallas as pallas_mod
    real = pallas_mod.pallas_call

    def record(kernel, *call_args, **kw):
        inner = real(kernel, *call_args, **kw)

        def run(*all_args):
            # grid and specs arrive as arguments or inside a grid_spec,
            # whose scalar-prefetch operands lead the call's
            spec = kw.get("grid_spec")
            given = kw.get if spec is None else functools.partial(getattr,
                                                                  spec)
            n_scalars = getattr(spec, "num_scalar_prefetch", 0)
            try:
                scalars = [np.asarray(a) for a in all_args[:n_scalars]]
            except Exception:       # tracers: traced under a jit
                scalars = None
            args = all_args[n_scalars:]
            into.append(KernelSite(
                name=_kernel_name(kernel),
                grid=tuple(int(g) for g in _as_seq(given("grid"))),
                in_specs=_as_seq(given("in_specs")),
                out_specs=_as_seq(given("out_specs")),
                scalar_prefetch=scalars,
                in_shapes=[(tuple(int(d) for d in a.shape),
                            str(a.dtype)) for a in args],
                out_shapes=[(tuple(int(d) for d in s.shape),
                             str(np.dtype(s.dtype)))
                            for s in _as_seq(kw.get("out_shape"))],
                input_output_aliases=dict(
                    kw.get("input_output_aliases") or {})))
            return inner(*all_args)
        return run

    pallas_mod.pallas_call = record
    try:
        yield
    finally:
        pallas_mod.pallas_call = real


def _block_shape(spec) -> Optional[Tuple[int, ...]]:
    bs = getattr(spec, "block_shape", None)
    if bs is None:
        return None
    return tuple(int(b) for b in bs)


def _check_operand(site: KernelSite, kind: str, i: int, spec,
                   shape: Tuple[int, ...], problems: List[str]):
    block = _block_shape(spec)
    if block is None:
        return                       # scalar/SMEM spec: nothing blocked
    if len(block) != len(shape):
        problems.append(
            f"{site.name}: {kind}[{i}] block shape {block} rank != "
            f"operand shape {shape}")
        return
    n_blocks, ragged = [], {}
    for d, (s, b) in enumerate(zip(shape, block)):
        if b < 1:
            problems.append(
                f"{site.name}: {kind}[{i}] block dim {d} is {b}")
            return
        if s % b != 0:
            ragged[d] = (
                f"{site.name}: {kind}[{i}] dim {d} (= {s}) not "
                f"divisible by block {b} — the kernel reads/writes "
                f"partial tiles (missing pad?)")
        n_blocks.append(max(1, s // b))
    index_map = getattr(spec, "index_map", None)
    if index_map is None or not site.grid:
        problems.extend(ragged.values())
        return
    # with an index map the whole blocks bound the indices below, so a
    # ragged dim is a fault only where a step reaches its partial block
    # (an operand read by column blocks out of a wider array is not)
    # evaluate the index map at every grid corner: the extremes bound
    # the affine maps these kernels use, so a step past the last block
    # shows up at a corner.  A map that looks its block up in
    # scalar-prefetch operands is bounded by no corner: every point then
    if site.scalar_prefetch is None:
        problems.append(
            f"{site.name}: {kind}[{i}] index_map reads scalar-prefetch "
            f"operands that were traced without values: trace the launch "
            f"outside a jit so that it can be evaluated")
        return
    scalars = list(site.scalar_prefetch)
    corners = itertools.product(
        *(range(g) if scalars else sorted({0, g - 1}) for g in site.grid))
    for corner in corners:
        try:
            idx = index_map(*corner, *scalars)
        except Exception as e:       # a map that cannot even evaluate
            problems.append(
                f"{site.name}: {kind}[{i}] index_map failed at grid "
                f"point {corner}: {e}")
            return
        idx = tuple(int(v) for v in _as_seq(idx))
        if len(idx) != len(block):
            problems.append(
                f"{site.name}: {kind}[{i}] index_map returns "
                f"{len(idx)} indices for a rank-{len(block)} block")
            return
        for d, (v, n) in enumerate(zip(idx, n_blocks)):
            if v == n and d in ragged:
                if ragged[d] not in problems:
                    problems.append(ragged[d])
            elif not (0 <= v < n):
                problems.append(
                    f"{site.name}: {kind}[{i}] index_map at grid "
                    f"point {corner} returns block index {v} for dim "
                    f"{d} — out of [0, {n}) (shape {shape}, block "
                    f"{block})")


def check_site(site: KernelSite) -> List[str]:
    """Lint one recorded launch; returns problem strings (empty =
    clean)."""
    problems: List[str] = []
    for i, (spec, (shape, _)) in enumerate(zip(site.in_specs,
                                               site.in_shapes)):
        _check_operand(site, "in_specs", i, spec, shape, problems)
    for i, (spec, (shape, _)) in enumerate(zip(site.out_specs,
                                               site.out_shapes)):
        _check_operand(site, "out_specs", i, spec, shape, problems)
    if len(site.in_specs) != len(site.in_shapes):
        problems.append(
            f"{site.name}: {len(site.in_specs)} in_specs for "
            f"{len(site.in_shapes)} operands")
    if len(site.out_specs) != len(site.out_shapes):
        problems.append(
            f"{site.name}: {len(site.out_specs)} out_specs for "
            f"{len(site.out_shapes)} outputs")
    # aliasing: each output donated to at most ONE input, indices in
    # range, shape/dtype agreement on the pair
    seen_out: Dict[int, int] = {}
    for in_idx, out_idx in site.input_output_aliases.items():
        in_idx, out_idx = int(in_idx), int(out_idx)
        if not (0 <= in_idx < len(site.in_shapes)):
            problems.append(
                f"{site.name}: alias input index {in_idx} out of "
                f"range (kernel has {len(site.in_shapes)} inputs)")
            continue
        if not (0 <= out_idx < len(site.out_shapes)):
            problems.append(
                f"{site.name}: alias output index {out_idx} out of "
                f"range (kernel has {len(site.out_shapes)} outputs)")
            continue
        if out_idx in seen_out:
            problems.append(
                f"{site.name}: output {out_idx} aliased twice "
                f"(inputs {seen_out[out_idx]} and {in_idx}) — two "
                f"refs racing one buffer")
            continue
        seen_out[out_idx] = in_idx
        in_shape, in_dt = site.in_shapes[in_idx]
        out_shape, out_dt = site.out_shapes[out_idx]
        if in_shape != out_shape or in_dt != out_dt:
            problems.append(
                f"{site.name}: alias {in_idx}->{out_idx} shape/dtype "
                f"mismatch ({in_dt}{list(in_shape)} vs "
                f"{out_dt}{list(out_shape)})")
    return problems


# -- driving the real kernel family ---------------------------------------

def _clear_jit_caches(*modules):
    """Defeat ``jax.jit``'s trace cache on every wrapper in the given
    modules: a warm cache means ``pallas_call`` never re-runs and the
    recorder sees nothing."""
    for mod in modules:
        for v in vars(mod).values():
            clear = getattr(v, "clear_cache", None)
            if callable(clear):
                try:
                    clear()
                except Exception:
                    pass


def collect_kernel_sites() -> List[KernelSite]:
    """Trace every public kernel wrapper in ``ops/pallas_*.py`` on tiny
    operands and return the recorded launch sites.  Runs in interpret
    mode on CPU (the kernels already route there off-TPU), so this is
    cheap enough for a tier-1 test."""
    import jax
    import jax.numpy as jnp
    from ..ops import (pallas_adam, pallas_common, pallas_flash_attention,
                       pallas_grouped_matmul, pallas_lamb,
                       pallas_layer_norm, pallas_multi_tensor, pallas_rope,
                       pallas_short_conv, pallas_ssd)

    _clear_jit_caches(pallas_adam, pallas_flash_attention,
                      pallas_grouped_matmul, pallas_lamb, pallas_layer_norm,
                      pallas_multi_tensor, pallas_rope, pallas_short_conv,
                      pallas_ssd)
    sites: List[KernelSite] = []
    rng = np.random.RandomState(18)
    f32 = lambda *s: jnp.asarray(rng.randn(*s), jnp.float32)
    with capture_kernel_sites(sites):
        # fused Adam, fp32-only and with the fused half write-out (the
        # two out_specs arities)
        n = 1000
        p, m, v, g = f32(n), np.abs(f32(n)), np.abs(f32(n)), f32(n)
        pallas_adam.fused_adam(p, m, v, g, 1e-3, 1.0, 0.9, 0.999, 1e-8,
                               False, 0.0)
        pallas_adam.fused_adam(p, m, v, g, 1e-3, 1.0, 0.9, 0.999, 1e-8,
                               False, 0.01, half_dtype=jnp.bfloat16)
        # LAMB, both stages (stage1 aliases 2 of 3 outputs, stage2 1/1)
        pallas_lamb.lamb_stage1(g, p, m, v, jnp.float32(1.0),
                                jnp.float32(1.0), jnp.float32(1.0),
                                0.9, 0.999, 1.0, 1e-6, 0.01, True)
        pallas_lamb.lamb_stage2(p, g, jnp.ones_like(p),
                                jnp.float32(1e-3))
        # layer norm fwd + bwd (column-stat specs next to row blocks)
        x2 = f32(8, 32)
        w, b = f32(32), f32(32)
        y, mean, inv = pallas_layer_norm.forward(x2, w, b, 1e-5)
        pallas_layer_norm.backward(f32(8, 32), x2, w, b, mean, inv)
        # multi-tensor family (SMEM scalar + finite-flag accumulators)
        tree = {"a": f32(300), "b": f32(40)}
        pallas_multi_tensor.multi_tensor_scale(tree, 2.0)
        pallas_multi_tensor.multi_tensor_axpby(1.0, 2.0, tree, tree)
        pallas_multi_tensor.multi_tensor_l2norm(tree)
        # flash attention fwd + bwd (the 3-kernel family with its
        # blocked T x D streaming)
        q = f32(1, 2, 128, 64)
        k = f32(1, 2, 128, 64)
        vv = f32(1, 2, 128, 64)
        jax.grad(lambda a: jnp.sum(
            pallas_flash_attention.flash_attention(a, k, vv,
                                                   causal=True)))(q)
        # the same three on token-major operands with K/V once per K/V
        # head: fp32 heads go one a step, so a group's six query heads
        # are six steps of dk/dv's sequential axis (the chunked index
        # maps, over Python ints); bf16 heads share a step and its K/V
        # block, under a band with dead steps
        tm = pallas_flash_attention.flash_attention_token_major
        q, k = f32(1, 384, 6, 128), f32(1, 384, 1, 128)
        jax.grad(lambda a: jnp.sum(tm(a, k, k, causal=True)))(q)
        q, k = (f32(2, 512, 8, 128).astype(jnp.bfloat16),
                f32(2, 512, 2, 128).astype(jnp.bfloat16))
        jax.grad(lambda a: jnp.sum(
            tm(a, k, k, causal=True, window=200).astype(jnp.float32)))(q)
        # a score head of a lane tile and a half: q and k at the value
        # head's 128 with the trailing 64 beside them, two heads a lane
        # tile, one rope key head for all query heads (dk's rope part
        # leaves as a float32 tile a leading step)
        q, qr = (f32(2, 256, 4, d).astype(jnp.bfloat16) for d in (128, 64))
        kr = f32(2, 256, 1, 64).astype(jnp.bfloat16)
        jax.grad(lambda a, b: jnp.sum(tm(
            a, q, q, causal=True, q_rope=qr, k_rope=b).astype(jnp.float32)),
            (0, 1))(q, kr)
        # rotary embedding on a projection's output, half of each head
        ang = f32(64, 32)
        pallas_rope.rope_token_major(
            f32(2, 64, 3 * 128), jnp.cos(ang), jnp.sin(ang), 128)
        # the selective scan's pair: a chunk of a group of heads a step,
        # two chunks of two groups of two heads sharing a lane tile, the
        # backward's index maps walking the chunks in reverse
        xs, bc = f32(1, 256, 4, 64), f32(1, 256, 2, 128)
        jax.grad(lambda a: jnp.sum(pallas_ssd.ssd_scan(
            a, np.abs(f32(1, 256, 4)) * 0.1, -np.abs(f32(4)), bc, bc, f32(4),
            128)))(xs)
        # the short convolution's pair in both forms: a step's block and
        # the 16-row halo blocks before and after it, clamped at the
        # sequence's ends (three blocks of tokens: both clamps and an
        # interior step); the gated backward's last axis walks the three
        # parts of the one cotangent; the silu form reads its columns from
        # an offset inside a projection whose width is not whole blocks
        rows3 = 3 * pallas_short_conv._ROWS
        jax.grad(lambda a: jnp.sum(pallas_short_conv.short_conv(
            a, f32(3, 128), form="gated")))(f32(2, rows3, 3 * 128))
        jax.grad(lambda a: jnp.sum(pallas_short_conv.short_conv(
            a, f32(4, 256), f32(256), form="silu", offset=128)))(
                f32(1, rows3, 128 + 256 + 64))
        # the grouped products, forward and both gradients: their index
        # maps look blocks up in work items computed from the groups'
        # sizes, so one trace a representative split of the rows (all in
        # the first group, all in the last, the cells' near-uniform one
        # with a dead tail, none at all), outside the launches' jit so
        # that the items reach the recorder as values
        rows, stack = f32(512, 256), f32(4, 256, 128)
        for sizes in ((512, 0, 0, 0), (0, 0, 0, 512), (70, 61, 66, 59),
                      (0, 0, 0, 0)):
            items = pallas_grouped_matmul.work_items(
                jnp.asarray(sizes, jnp.int32), 512, 128)
            with jax.disable_jit():
                jax.grad(lambda a, w, items=items: jnp.sum(
                    pallas_grouped_matmul.grouped_matmul(a, w, items, 128)),
                    (0, 1))(rows, stack)
    return sites


def lint_pallas_kernels() -> Tuple[List[KernelSite], List[str]]:
    """Collect every launch site and lint them all.  Returns
    ``(sites, problems)`` — tests assert sites are non-trivial AND
    problems empty, so a refactor that silently stops launching
    kernels fails as loudly as one that breaks a precondition."""
    sites = collect_kernel_sites()
    problems: List[str] = []
    for s in sites:
        problems.extend(check_site(s))
    return sites, problems
