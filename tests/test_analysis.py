"""Mutation tests for apex_tpu.analysis: every rule must FLAG its
deliberately-broken graph and PASS its fixed twin — no rule is allowed
to pass vacuously.

The clean-repo assertions (zero findings over the real entry-point
registry) live in tests/test_step_graph_audit.py; here we feed the rule
engine synthetic entry points with seeded violations: a host sync
smuggled into a scan body, an un-donated cache, a blocklisted
``cur_len`` donation, a shared-buffer double donation, a forced fp32
conv under an O2 expectation, an injected activation transpose, and a
comm pattern that disagrees with its accounting.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu import analysis, serving
from apex_tpu.analysis import EntryPoint, Graph
from apex_tpu.observability import exporters


def _ep(name, expect=None, **graph_kw):
    ep = EntryPoint(name, lambda ep: Graph(**graph_kw), expect=expect)
    return ep


def _run(ep, rule):
    return analysis.analyze_entry_point(ep, rules=[rule])


# -- host-transfer rule ---------------------------------------------------

def test_host_transfer_rule_flags_seeded_callback():
    """A pure_callback inside the scanned decode body is exactly the
    per-tick host sync the serving window exists to kill — the rule
    must see through the scan."""
    def tick(carry, _):
        y = jax.pure_callback(
            lambda a: np.asarray(a),
            jax.ShapeDtypeStruct(carry.shape, carry.dtype), carry)
        return y + 1.0, y.sum()

    def stepped(x):
        out, ys = jax.lax.scan(tick, x, None, length=4)
        return out, ys

    ep = _ep("mutant_host_sync",
             trace=lambda: jax.make_jaxpr(stepped)(jnp.ones(8)))
    found = _run(ep, "host-transfer")
    assert len(found) == 1
    assert found[0].severity == "error"
    assert found[0].detail["primitive"] == "pure_callback"
    assert found[0].detail["count"] == 1      # scan body counted once

    clean = _ep("clean_host_sync",
                trace=lambda: jax.make_jaxpr(
                    lambda x: jax.lax.scan(
                        lambda c, _: (c + 1.0, c.sum()), x, None,
                        length=4))(jnp.ones(8)))
    assert _run(clean, "host-transfer") == []


def test_host_transfer_rule_optout():
    ep = _ep("optout", expect={"allow_host_transfers": True},
             trace=lambda: None)
    assert not analysis.get_rule("host-transfer").applies(ep)


# -- donation rule --------------------------------------------------------

def _donation_ep(name, fn, donate, args, arg_names, expect_donation):
    jitted = jax.jit(fn, donate_argnums=donate)
    return _ep(name, expect={"donation": expect_donation},
               trace=lambda: jax.make_jaxpr(fn)(*args),
               lower=lambda: jitted.lower(*args),
               arg_names=arg_names, example_args=args)


def _bump(ids, cache):
    return ids + 1, jax.tree_util.tree_map(lambda c: c + 1.0, cache)


def test_donation_rule_flags_unaliased_cache():
    """An entry point that promises a donated KV cache but whose jit
    forgot donate_argnums: without donation XLA keeps a second copy of
    the multi-GB cache alive across every dispatch."""
    cache = {"0": jnp.zeros((2, 8)), "1": jnp.zeros((2, 8))}
    args = (jnp.zeros((2, 4), jnp.int32), cache)
    broken = _donation_ep("mutant_undonated", _bump, (), args,
                          ("ids", "cache"),
                          {"expect_donated": ("ids", "cache")})
    found = _run(broken, "donation")
    assert {f.detail.get("argument") for f in found} == {"ids", "cache"}
    assert all(f.severity == "error" for f in found)

    fixed = _donation_ep("fixed_donated", _bump, (0, 1), args,
                         ("ids", "cache"),
                         {"expect_donated": ("ids", "cache")})
    assert _run(fixed, "donation") == []


def test_donation_rule_flags_blocklisted_cur_len():
    """Donating the per-slot length vector is the PR 2 compile-cache
    corruption; serving.DONATION_BLOCKLIST pins it permanently and the
    rule enforces it even when the entry point's own expectation
    forgot to mention cur_len."""
    assert "cur_len" in serving.DONATION_BLOCKLIST
    assert "n_new" in serving.DONATION_BLOCKLIST

    def stepish(cur_len, cache):
        return cur_len + 1, jax.tree_util.tree_map(lambda c: c + 1.0,
                                                   cache)

    cache = {"k": jnp.zeros((2, 8))}
    args = (jnp.zeros((2,), jnp.int32), cache)
    broken = _donation_ep("mutant_blocklist", stepish, (0, 1), args,
                          ("cur_len", "cache"),
                          {"expect_donated": ("cache",)})
    found = _run(broken, "donation")
    assert len(found) == 1
    assert found[0].detail["argument"] == "cur_len"
    assert found[0].detail["blocklisted"] is True

    fixed = _donation_ep("fixed_blocklist", stepish, (1,), args,
                         ("cur_len", "cache"),
                         {"expect_donated": ("cache",)})
    assert _run(fixed, "donation") == []


def test_donation_rule_flags_undonated_block_pool():
    """PR 17 mutation: a paged decode window whose jit forgot to
    donate the block POOL — the one buffer that dwarfs everything
    else — must be flagged; the kv_len/n_blk length vectors joined
    cur_len/n_new on the permanent blocklist (same PR 2 corruption
    class: per-slot int32 state the compile cache must never alias)."""
    assert "kv_len" in serving.DONATION_BLOCKLIST
    assert "n_blk" in serving.DONATION_BLOCKLIST

    def paged_stepish(ids, pool, tables, free_stack):
        dense = jax.tree_util.tree_map(lambda p: p[tables].sum(), pool)
        return (ids + 1,
                jax.tree_util.tree_map(lambda p: p + 1.0, pool),
                dense, free_stack)

    pool = {"k": jnp.zeros((6, 2, 4, 8)), "v": jnp.zeros((6, 2, 4, 8))}
    args = (jnp.zeros((2, 16), jnp.int32), pool,
            jnp.zeros((2, 3), jnp.int32), jnp.arange(6))
    names = ("ids", "pool", "tables", "free_stack")
    expect = {"expect_donated": ("ids", "pool"),
              "forbid_donated": ("tables", "free_stack")}
    broken = _donation_ep("mutant_undonated_pool", paged_stepish, (0,),
                          args, names, expect)
    found = _run(broken, "donation")
    assert {f.detail.get("argument") for f in found} == {"pool"}
    assert all(f.severity == "error" for f in found)

    fixed = _donation_ep("fixed_donated_pool", paged_stepish, (0, 1),
                         args, names, expect)
    assert _run(fixed, "donation") == []

    # donating a blocklisted paged length vector is flagged even when
    # the expectation forgot to forbid it
    def lenish(kv_len, pool):
        return kv_len + 1, jax.tree_util.tree_map(lambda p: p + 1.0,
                                                  pool)

    largs = (jnp.zeros((2,), jnp.int32), {"k": jnp.zeros((6, 8))})
    bad_len = _donation_ep("mutant_kv_len", lenish, (0, 1), largs,
                           ("kv_len", "pool"),
                           {"expect_donated": ("pool",)})
    found = _run(bad_len, "donation")
    assert len(found) == 1
    assert found[0].detail["argument"] == "kv_len"
    assert found[0].detail["blocklisted"] is True


def test_donation_rule_flags_double_donation():
    """The gpt init_cache gotcha: a zeros buffer shared across layers
    (dict(layer) shallow copy) donated once per layer — XLA rejects
    'Attempt to donate the same buffer twice' only at compile time;
    the rule catches it statically from the example args."""
    shared = jnp.zeros((2, 8))
    cache = {"0": {"k": shared}, "1": {"k": shared}}   # the bug
    args = (jnp.zeros((2, 4), jnp.int32), cache)
    broken = _donation_ep("mutant_double", _bump, (0, 1), args,
                          ("ids", "cache"),
                          {"expect_donated": ("ids", "cache")})
    found = _run(broken, "donation")
    assert len(found) == 1
    assert "shares a buffer" in found[0].detail["duplicate"]

    per_layer = {"0": {"k": jnp.zeros((2, 8))},
                 "1": {"k": jnp.zeros((2, 8))}}
    fixed = _donation_ep("fixed_double", _bump, (0, 1),
                         (jnp.zeros((2, 4), jnp.int32), per_layer),
                         ("ids", "cache"),
                         {"expect_donated": ("ids", "cache")})
    assert _run(fixed, "donation") == []


def test_donation_rule_flags_forbidden_argument():
    args = (jnp.zeros((2, 4), jnp.int32), {"k": jnp.zeros((2, 8))})
    broken = _donation_ep("mutant_forbidden", _bump, (0, 1), args,
                          ("ids", "cache"),
                          {"expect_donated": ("cache",),
                           "forbid_donated": ("ids",)})
    found = _run(broken, "donation")
    assert len(found) == 1
    assert found[0].detail["argument"] == "ids"
    assert found[0].detail["blocklisted"] is False


# -- amp dtype rule -------------------------------------------------------

def _conv_graph(dtype):
    def f(x, w):
        return jax.lax.conv_general_dilated(
            x, w, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
    x = jnp.ones((2, 8, 8, 4), dtype)
    w = jnp.ones((3, 3, 4, 4), dtype)
    return lambda: jax.make_jaxpr(f)(x, w)


_O2_AMP = {"opt_level": "O2", "conv_dtype": "bfloat16", "min_convs": 1}


def test_amp_rule_flags_forced_fp32_conv():
    broken = _ep("mutant_fp32_conv", expect={"amp": dict(_O2_AMP)},
                 trace=_conv_graph(jnp.float32))
    found = _run(broken, "amp-dtype")
    assert len(found) == 1
    assert found[0].detail == {"lhs": "float32", "rhs": "float32",
                               "count": 1, "expected": "bfloat16"}

    fixed = _ep("fixed_bf16_conv", expect={"amp": dict(_O2_AMP)},
                trace=_conv_graph(jnp.bfloat16))
    assert _run(fixed, "amp-dtype") == []


def test_amp_rule_vacuity_guard():
    """A graph with no convs under a conv expectation is a finding,
    not a silent pass — the floor keeps every rule non-vacuous."""
    empty = _ep("mutant_convless", expect={"amp": dict(_O2_AMP)},
                trace=lambda: jax.make_jaxpr(lambda x: x * 2.0)(
                    jnp.ones((4,), jnp.bfloat16)))
    found = _run(empty, "amp-dtype")
    assert len(found) == 1
    assert "vacuous" in found[0].message


def test_amp_rule_flags_fp32_dot():
    def f(a, b):
        return a @ b
    broken = _ep("mutant_fp32_dot",
                 expect={"amp": {"opt_level": "O2",
                                 "dot_dtype": "bfloat16",
                                 "min_dots": 1}},
                 trace=lambda: jax.make_jaxpr(f)(
                     jnp.ones((32, 32)), jnp.ones((32, 32))))
    found = _run(broken, "amp-dtype")
    assert len(found) == 1
    assert found[0].detail["operands"] == ["float32", "float32"]


# -- layout rule ----------------------------------------------------------

_LAYOUT = {"min_activation_elems": 256, "allowed_6d_rearranges": 0}


def test_layout_rule_flags_injected_transpose():
    def leaky(x):
        return jnp.transpose(x, (0, 3, 1, 2)).sum()   # NHWC -> NCHW

    broken = _ep("mutant_transpose", expect={"layout": dict(_LAYOUT)},
                 trace=lambda: jax.make_jaxpr(leaky)(
                     jnp.ones((2, 8, 8, 4))))
    found = _run(broken, "layout")
    assert len(found) == 1
    assert found[0].detail["shape"] == [2, 8, 8, 4]
    assert found[0].detail["permutation"] == [0, 3, 1, 2]

    fixed = _ep("fixed_transpose", expect={"layout": dict(_LAYOUT)},
                trace=lambda: jax.make_jaxpr(lambda x: x.sum())(
                    jnp.ones((2, 8, 8, 4))))
    assert _run(fixed, "layout") == []


def test_layout_rule_6d_budget():
    def s2d_like(x):
        b, h, w, c = x.shape
        y = x.reshape(b, h // 2, 2, w // 2, 2, c)
        return jnp.transpose(y, (0, 1, 3, 2, 4, 5)).sum()

    over = _ep("mutant_6d", expect={"layout": dict(_LAYOUT)},
               trace=lambda: jax.make_jaxpr(s2d_like)(
                   jnp.ones((2, 8, 8, 4))))
    found = _run(over, "layout")
    assert len(found) == 1
    assert found[0].detail == {"count": 1, "budget": 0}

    budgeted = _ep("fixed_6d",
                   expect={"layout": dict(_LAYOUT,
                                          allowed_6d_rearranges=1)},
                   trace=lambda: jax.make_jaxpr(s2d_like)(
                       jnp.ones((2, 8, 8, 4))))
    assert _run(budgeted, "layout") == []


# -- flop accounting rule -------------------------------------------------

def test_flop_rule_flags_unexplained_delta():
    """A graph that traces twice the budgeted FLOPs is work nobody
    accounted for — the ZeRO/paged-KV refactors must not silently grow
    the step."""
    a = jnp.ones((32, 32))
    one_dot = 2 * 32 * 32 * 32

    broken = _ep("mutant_flop_delta",
                 expect={"flops": {"expected_flops": one_dot,
                                   "rtol": 0.05}},
                 trace=lambda: jax.make_jaxpr(lambda a, b: a @ b @ b)(
                     a, a))
    found = _run(broken, "flop-accounting")
    assert len(found) == 1
    assert "unexplained FLOP delta" in found[0].message
    assert found[0].detail["flops"] == 2 * one_dot

    fixed = _ep("fixed_flop_delta",
                expect={"flops": {"expected_flops": one_dot,
                                  "rtol": 0.05}},
                trace=lambda: jax.make_jaxpr(lambda a, b: a @ b)(a, a))
    assert _run(fixed, "flop-accounting") == []


def test_flop_rule_flags_fp32_matmul_fraction():
    """The flops-weighted upcast check: a forced fp32 conv under a
    bf16-policy cap carries 100% of the matmul FLOPs in fp32."""
    expect = {"flops": {"max_fp32_matmul_fraction": 0.02,
                        "min_matmul_flops": 1}}
    broken = _ep("mutant_fp32_flops", expect={"flops": dict(expect["flops"])},
                 trace=_conv_graph(jnp.float32))
    found = _run(broken, "flop-accounting")
    assert len(found) == 1
    assert found[0].detail["fp32_matmul_fraction"] == 1.0

    fixed = _ep("fixed_bf16_flops", expect={"flops": dict(expect["flops"])},
                trace=_conv_graph(jnp.bfloat16))
    assert _run(fixed, "flop-accounting") == []


def test_flop_rule_vacuity_guard():
    empty = _ep("mutant_matmulless",
                expect={"flops": {"max_fp32_matmul_fraction": 0.02,
                                  "min_matmul_flops": 1}},
                trace=lambda: jax.make_jaxpr(lambda x: x * 2.0)(
                    jnp.ones((4,))))
    found = _run(empty, "flop-accounting")
    assert len(found) == 1
    assert "vacuous" in found[0].message


# -- memory budget rule ---------------------------------------------------

def test_memory_rule_flags_seeded_over_budget():
    """A seeded over-budget graph (triple-copy temp) flags; the same
    graph under an honest budget passes."""
    def bloated(x):
        big = jnp.concatenate([x, x, x])
        return big.sum()

    trace = lambda: jax.make_jaxpr(bloated)(jnp.ones((1024,)))  # noqa: E731
    # args 4KB + 12KB temp = 16KB peak; budget 8KB flags
    broken = _ep("mutant_over_budget",
                 expect={"memory": {"budget_bytes": 8 * 1024}},
                 trace=trace)
    found = _run(broken, "memory-budget")
    assert len(found) == 1
    assert found[0].detail["peak_live_bytes"] > 8 * 1024
    assert found[0].severity == "error"

    fixed = _ep("fixed_over_budget",
                expect={"memory": {"budget_bytes": 32 * 1024}},
                trace=trace)
    assert _run(fixed, "memory-budget") == []


def test_memory_rule_flags_live_to_argument_ratio():
    def dup(x):
        return jnp.concatenate([x, x, x, x]).sum()

    broken = _ep("mutant_ratio",
                 expect={"memory": {"max_live_to_argument_ratio": 3.0}},
                 trace=lambda: jax.make_jaxpr(dup)(jnp.ones((1024,))))
    found = _run(broken, "memory-budget")
    assert len(found) == 1
    assert found[0].detail["ratio"] > 3.0

    lean = _ep("fixed_ratio",
               expect={"memory": {"max_live_to_argument_ratio": 3.0}},
               trace=lambda: jax.make_jaxpr(lambda x: (x * 2).sum())(
                   jnp.ones((1024,))))
    assert _run(lean, "memory-budget") == []


def test_memory_rule_flags_fp32_upcast_under_o2():
    """The fp32-upcast mutation: the same matmul pipeline with operands
    upcast to fp32 doubles the fp32 temp bytes and fails lint; the
    bf16 twin passes under the same budget."""
    w = jnp.ones((256, 256), jnp.bfloat16)
    x = jnp.ones((64, 256), jnp.bfloat16)

    def clean(x):
        h = jnp.maximum(x @ w, 0)
        return (h @ w).astype(jnp.float32).sum()

    def upcast(x):
        h = jnp.maximum(x.astype(jnp.float32) @ w.astype(jnp.float32),
                        0)
        return (h @ w.astype(jnp.float32)).sum()

    from apex_tpu.observability import memory as obsmem
    clean_f32 = obsmem.jaxpr_live_bytes(jax.make_jaxpr(clean)(x))[
        "peak_temp_bytes_by_dtype"].get("float32", 0)
    budget = {"memory": {"temp_budget_bytes_by_dtype":
                         {"float32": 2 * max(clean_f32, 1)}}}
    broken = _ep("mutant_fp32_upcast", expect=dict(budget),
                 trace=lambda: jax.make_jaxpr(upcast)(x))
    found = _run(broken, "memory-budget")
    assert len(found) == 1
    assert found[0].detail["dtype"] == "float32"
    assert found[0].detail["peak_temp_bytes"] > \
        found[0].detail["budget_bytes"]

    fixed = _ep("fixed_bf16_pipeline", expect=dict(budget),
                trace=lambda: jax.make_jaxpr(clean)(x))
    assert _run(fixed, "memory-budget") == []


# -- collective accounting rule -------------------------------------------

def _psum_graph(n_psums):
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))

    def f(x):
        for _ in range(n_psums):
            x = jax.lax.psum(x, "data")
        return x

    mapped = jax.shard_map(f, mesh=mesh, in_specs=(P("data"),),
                           out_specs=P("data"), check_vma=False)
    return lambda: jax.make_jaxpr(mapped)(jnp.ones((4, 8)))


def test_collective_rule_flags_wrong_count_and_payload():
    """An algorithm that assumes 2 allreduces but traces 1 (or moves
    the wrong number of bytes) is a wrong answer, not a perf bug —
    exactly what adaptive-summation-style schemes depend on."""
    broken = _ep("mutant_collective",
                 expect={"collectives": {"counts": {"psum": 2},
                                         "payload_bytes": 2 * 2 * 8 * 4}},
                 trace=_psum_graph(1))
    found = _run(broken, "collective")
    assert {f.detail.get("primitive", "payload")
            for f in found} == {"psum", "payload"}
    count = [f for f in found if "primitive" in f.detail][0]
    assert (count.detail["expected"], count.detail["got"]) == (2, 1)

    fixed = _ep("fixed_collective",
                expect={"collectives": {"counts": {"psum": 2},
                                        "payload_bytes": 2 * 2 * 8 * 4}},
                trace=_psum_graph(2))
    assert _run(fixed, "collective") == []


def test_collective_rule_flags_unbudgeted_collective():
    """A collective primitive the expectation never mentioned is
    budgeted at zero — a smuggled all-gather can't hide."""
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    mapped = jax.shard_map(
        lambda x: jax.lax.all_gather(x, "data"), mesh=mesh,
        in_specs=(P("data"),), out_specs=P(), check_vma=False)
    ep = _ep("mutant_unbudgeted",
             expect={"collectives": {"counts": {}}},
             trace=lambda: jax.make_jaxpr(mapped)(jnp.ones((4, 8))))
    found = _run(ep, "collective")
    assert len(found) == 1
    assert found[0].detail["primitive"] == "all_gather"


def test_collective_rule_interleaving_mutation_both_ways():
    """The PR 14 overlap pin, mutation-proofed in both directions: the
    REAL staged step traced with overlap=False (reduce-after-backward
    — identical census, identical payloads, only eqn positions differ)
    must flag under the overlap-derived expectations, and the real
    overlapped step must lint clean under the same expectations."""
    from apex_tpu import parallel
    from apex_tpu.analysis.entry_points import _staged_mlp_graph

    sched = parallel.overlap_comm_schedule(
        [{"w": jax.ShapeDtypeStruct((32, 32), jnp.float32),
          "b": jax.ShapeDtypeStruct((32,), jnp.float32)}] * 4,
        comm_topology="hierarchical", ici_size=4, world=8, nproc=1,
        overlap=True)
    overlap_expect = {"collectives":
                      parallel.overlap_collective_expectations(
                          sched, extra_psums=2, extra_psum_bytes=8)}

    broken = EntryPoint("mutant_reduce_after_backward",
                        lambda ep: _staged_mlp_graph(ep, overlap=False),
                        expect=dict(overlap_expect))
    found = _run(broken, "collective")
    assert len(found) == 1, found
    assert "reduce-after-backward schedule" in found[0].message
    assert found[0].detail["first_collective_eqn"] > \
        found[0].detail["last_matmul_eqn"]

    fixed = EntryPoint("fixed_overlapped",
                       lambda ep: _staged_mlp_graph(ep, overlap=True),
                       expect=dict(overlap_expect))
    assert _run(fixed, "collective") == []


def test_collective_rule_interleaving_vacuity_guards():
    """An interleaving expectation over a graph with no gradient-sized
    collective (or no matmuls at all) is a finding, not a silent pass
    — the pin must not evaporate when the graph changes shape."""
    no_coll = _ep(
        "mutant_interleave_no_collective",
        expect={"collectives": {"counts": {},
                                "interleaving":
                                {"min_payload_bytes": 64}}},
        trace=lambda: jax.make_jaxpr(
            lambda x: jnp.tanh(x @ x))(jnp.ones((8, 8))))
    found = _run(no_coll, "collective")
    assert any("vacuous interleaving" in f.message for f in found)

    no_mm = _ep(
        "mutant_interleave_no_matmul",
        expect={"collectives": {"counts": {"psum": 1},
                                "payload_bytes": 2 * 8 * 4,
                                "interleaving":
                                {"min_payload_bytes": 16}}},
        trace=_psum_graph(1))
    found = _run(no_mm, "collective")
    assert any("no conv/dot" in f.message for f in found)


def test_numerics_rule_flags_host_sync_extra_collective_and_residue():
    """The PR 9 rule, mutation-proofed in all three directions: an
    'enabled' instrumentation that smuggles a host callback flags; one
    whose collective census exceeds baseline + planned digest delta
    flags; and a 'disabled' step that is NOT byte-identical to its
    baseline flags as residue.  The honest twins pass."""
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))

    def base_fn(x):
        return jax.lax.psum(x * 2.0, "data")

    def instrumented_fn(x):
        y = x * 2.0
        digest = jnp.stack([jnp.sum(y), jnp.sum(y * y)])
        return jax.lax.psum(y, "data") + jax.lax.psum(digest, "data")[0]

    def two_digests_fn(x):
        y = x * 2.0
        d = jnp.stack([jnp.sum(y), jnp.sum(y * y)])
        return (jax.lax.psum(y, "data")
                + jax.lax.psum(d, "data")[0]
                + jax.lax.psum(d * 2.0, "data")[1])

    def callback_fn(x):
        y = jax.pure_callback(
            lambda a: np.asarray(a),
            jax.ShapeDtypeStruct(x.shape, x.dtype), x)
        d = jnp.stack([jnp.sum(y), jnp.sum(y * y)])
        return jax.lax.psum(y, "data") + jax.lax.psum(d, "data")[0]

    def trace(fn):
        mapped = jax.shard_map(fn, mesh=mesh, in_specs=(P("data"),),
                               out_specs=P(), check_vma=False)
        return lambda: jax.make_jaxpr(mapped)(jnp.ones((2, 8)))

    baseline = _ep("numerics_baseline", trace=trace(base_fn))
    enabled_expect = {"baseline": baseline, "enabled": True,
                      "extra_collectives": {"psum": 1},
                      "extra_payload_bytes": 2 * 4}
    ok = _ep("fixed_numerics", expect={"numerics": enabled_expect},
             trace=trace(instrumented_fn))
    assert _run(ok, "numerics") == []

    cb = _ep("mutant_numerics_callback",
             expect={"numerics": enabled_expect},
             trace=trace(callback_fn))
    found = _run(cb, "numerics")
    assert any(f.detail.get("primitive") == "pure_callback"
               for f in found)

    extra = _ep("mutant_numerics_extra_psum",
                expect={"numerics": enabled_expect},
                trace=trace(two_digests_fn))
    found = _run(extra, "numerics")
    assert any(f.detail.get("got") == 3 and f.detail.get("expected") == 2
               for f in found)
    assert any("payload" in f.message for f in found)

    # disabled: identical trace passes, residue flags
    off_ok = _ep("fixed_numerics_off",
                 expect={"numerics": {"baseline": baseline,
                                      "enabled": False}},
                 trace=trace(base_fn))
    assert _run(off_ok, "numerics") == []
    residue = _ep("mutant_numerics_residue",
                  expect={"numerics": {"baseline": baseline,
                                       "enabled": False}},
                  trace=trace(instrumented_fn))
    found = _run(residue, "numerics")
    assert len(found) == 1 and "residue" in found[0].message


def test_supervisor_rule_flags_instrumented_step_both_ways():
    """The PR 10 operational-plane rule, mutation-proofed in both
    directions like the numerics rule: the honest supervised step (an
    identity wrap, enabled or disabled) passes; a mutant 'supervisor'
    that smuggles a host callback into the step flags on BOTH the
    host-transfer census and the jaxpr identity; a mutant that merely
    adds eqns (extra collective, threaded state) flags as residue —
    again whether the expectation says enabled or disabled, because
    the supervisor contract is identical in both directions."""
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))

    def base_fn(x):
        return jax.lax.psum(x * 2.0, "data")

    def callback_fn(x):
        # a naive supervisor reading the loss per step from inside
        # the jitted graph — the exact mutation the rule exists for
        y = jax.pure_callback(
            lambda a: np.asarray(a),
            jax.ShapeDtypeStruct(x.shape, x.dtype), x)
        return jax.lax.psum(y * 2.0, "data")

    def extra_eqn_fn(x):
        y = x * 2.0
        return jax.lax.psum(y, "data") + jnp.sum(y) * 0.0

    def trace(fn):
        mapped = jax.shard_map(fn, mesh=mesh, in_specs=(P("data"),),
                               out_specs=P(), check_vma=False)
        return lambda: jax.make_jaxpr(mapped)(jnp.ones((2, 8)))

    baseline = _ep("supervisor_baseline", trace=trace(base_fn))
    for enabled in (True, False):
        expect = {"supervisor": {"baseline": baseline,
                                 "enabled": enabled}}
        ok = _ep(f"fixed_supervised_{enabled}", expect=expect,
                 trace=trace(base_fn))
        assert _run(ok, "supervisor") == []

        cb = _ep(f"mutant_supervised_callback_{enabled}",
                 expect=expect, trace=trace(callback_fn))
        found = _run(cb, "supervisor")
        assert any(f.detail.get("primitive") == "pure_callback"
                   for f in found)
        assert any("residue" in f.message for f in found)

        extra = _ep(f"mutant_supervised_residue_{enabled}",
                    expect=expect, trace=trace(extra_eqn_fn))
        found = _run(extra, "supervisor")
        assert len(found) == 1 and "residue" in found[0].message

    # a missing baseline cannot silently pass
    nobase = _ep("mutant_supervised_nobase",
                 expect={"supervisor": {"enabled": True}},
                 trace=trace(base_fn))
    found = _run(nobase, "supervisor")
    assert len(found) == 1 and "baseline" in found[0].message


def test_run_record_dispatch_in_mixed_stream():
    """A kind: run record interleaves in the telemetry stream and is
    validated by the run schema; its anomaly kinds stay in lockstep
    with the supervisor's tuple."""
    import json
    from apex_tpu.observability import exporters, supervisor
    assert exporters.RUN_ANOMALY_KINDS == supervisor.ANOMALY_KINDS
    good = exporters.JsonlExporter.enrich({
        "kind": "run", "run": "r", "verdict": "ok",
        "observations": 3, "watermark": 2,
        "anomaly_counts": {k: 0 for k in
                           exporters.RUN_ANOMALY_KINDS},
        "anomalies": []})
    lint = _enriched(analysis.Finding(
        rule="layout", entry_point="x", message="leak"))
    errs = exporters.validate_telemetry_jsonl(
        [json.dumps(good), json.dumps(lint)])
    assert errs == []
    bad = dict(good)
    bad["verdict"] = "attention"       # lies: zero counted anomalies
    errs = exporters.validate_telemetry_jsonl([json.dumps(bad)])
    assert any("inconsistent" in e for e in errs)


def test_numerics_record_dispatch_in_mixed_stream():
    """A kind: numerics record interleaves in the telemetry stream and
    dispatches to its own validator."""
    import json
    from apex_tpu.observability.exporters import (
        JsonlExporter, validate_telemetry_jsonl)
    good = JsonlExporter.enrich({
        "kind": "numerics", "metric": "mix", "steps": 1,
        "overflow_steps": 0,
        "layers": [{"name": "w", "nonfinite": 0, "abs_max": 1.0,
                    "grad_norm": 1.0, "underflow_fraction": 0.0}]})
    lint = _enriched(analysis.Finding(
        rule="layout", entry_point="x", message="leak"))
    assert validate_telemetry_jsonl(
        [json.dumps(lint), json.dumps(good)]) == []
    bad = dict(good)
    bad["overflow_steps"] = 7
    errs = validate_telemetry_jsonl([json.dumps(bad)])
    assert errs and any("exceeds steps" in e for e in errs)


def _hier_setup(ici=4, world=8):
    from apex_tpu.parallel import hierarchical_axis_groups
    mesh = Mesh(np.array(jax.devices()[:world]), ("data",))
    ici_groups, dcn_groups = hierarchical_axis_groups(world, ici)
    return mesh, ici_groups, dcn_groups


def test_collective_rule_flags_full_size_dcn_psum():
    """The tentpole's seeded mutation: a 'hierarchical' reduction that
    gathers BEFORE the cross-slice reduce — so a full-size psum sneaks
    onto DCN instead of the 1/ici shard.  Eqn counts match the honest
    plan exactly (1 reduce_scatter + 1 psum + 1 all_gather); only the
    per-primitive payload split — derived from allreduce_comm_plan via
    plan_collective_expectations — catches it."""
    from apex_tpu import parallel
    mesh, ici_groups, dcn_groups = _hier_setup()
    n = 1024

    def sneaky(x):
        # the axis-size scalar the real allreduce also traces, so the
        # mutant's EQN COUNTS match the honest graph exactly
        jax.lax.psum(jnp.ones((), jnp.float32), "data")
        shard = jax.lax.psum_scatter(x, "data", scatter_dimension=0,
                                     axis_index_groups=ici_groups,
                                     tiled=True)
        full = jax.lax.all_gather(shard, "data",
                                  axis_index_groups=ici_groups,
                                  tiled=True)
        return jax.lax.psum(full, "data",        # full n elems on DCN
                            axis_index_groups=dcn_groups)

    def honest(x):
        return parallel.allreduce_grads_tree(
            {"w": x}, "data", comm_topology="hierarchical", ici_size=4,
            gradient_average=False)["w"]

    plan = parallel.allreduce_comm_plan(
        {"w": jnp.zeros((n,), jnp.float32)},
        comm_topology="hierarchical", ici_size=4, world=8)
    # +1 psum / +4 bytes: the axis-size scalar
    expect = parallel.plan_collective_expectations(
        plan, extra_psums=1, extra_psum_bytes=4)

    def _trace(fn):
        mapped = jax.shard_map(fn, mesh=mesh, in_specs=(P(),),
                               out_specs=P(), check_vma=False)
        return lambda: jax.make_jaxpr(mapped)(jnp.ones((n,)))

    broken = _ep("mutant_fat_dcn_psum",
                 expect={"collectives": dict(expect)},
                 trace=_trace(sneaky))
    found = _run(broken, "collective")
    assert found, "full-size DCN psum must flag"
    # counts are identical by construction — no count finding fires
    assert not any("eqn(s)" in f.message for f in found)
    psum_f = [f for f in found if f.detail.get("primitive") == "psum"
              and "payload" in f.message][0]
    # the sneak moved ici x the bytes the plan budgeted for DCN
    assert psum_f.detail["payload_bytes"] == n * 4 + 4
    assert psum_f.detail["expected_bytes"] == n * 4 // 4 + 4

    fixed = _ep("fixed_hier_reduce",
                expect={"collectives": dict(expect)},
                trace=_trace(honest))
    assert _run(fixed, "collective") == []


def test_comm_plan_hierarchical_levels():
    """The static twin under comm_topology='hierarchical': per-level
    payloads, shard padding, the exact per-primitive eqn census, and
    the compressed variant halving ONLY the DCN hop."""
    from apex_tpu.parallel import (allreduce_comm_plan,
                                   plan_collective_expectations)
    grads = {"w": jnp.zeros((1001,), jnp.float32)}
    (flat,) = allreduce_comm_plan(grads)
    (h,) = allreduce_comm_plan(grads, comm_topology="hierarchical",
                               ici_size=4, world=8)
    assert h["topology"] == "hierarchical"
    assert (h["ici_size"], h["dcn_size"]) == (4, 2)
    assert h["wire_elements"] == 1004 and h["padded_elements"] == 3
    assert h["dcn_wire_bytes"] == (1004 // 4) * 4
    assert h["ici_wire_bytes"] == 1004 * 4 + (1004 // 4) * 4
    assert h["wire_bytes"] == h["ici_wire_bytes"] + h["dcn_wire_bytes"]
    assert h["eqns"] == {"reduce_scatter": 1, "psum": 1,
                         "all_gather": 1}
    assert h["eqn_payload_bytes"]["psum"] == h["dcn_wire_bytes"]
    # the headline relationship: DCN traffic shrinks
    # by exactly the ICI factor (modulo shard padding)
    assert h["dcn_wire_bytes"] * 4 == (flat["dcn_wire_bytes"]
                                       + h["padded_elements"] * 4)

    (c,) = allreduce_comm_plan(grads, comm_topology="hierarchical",
                               ici_size=4, world=8,
                               allreduce_compress_bf16=True)
    assert c["dcn_wire_bytes"] * 2 == h["dcn_wire_bytes"]
    assert c["dcn_comm_dtype"] == "bfloat16"
    assert c["eqns"] == {"reduce_scatter": 1, "all_gather": 2}
    assert c["ici_wire_bytes"] == h["ici_wire_bytes"]

    exp = plan_collective_expectations([h], extra_psums=2,
                                       extra_psum_bytes=8)
    assert exp["counts"] == {"reduce_scatter": 1, "psum": 3,
                             "all_gather": 1}
    assert exp["payload_bytes"] == h["wire_bytes"] + 8
    assert exp["payload_bytes_by_primitive"]["psum"] == \
        h["dcn_wire_bytes"] + 8

    # knob validation mirrors the runtime
    with pytest.raises(ValueError, match="world"):
        allreduce_comm_plan(grads, comm_topology="hierarchical",
                            ici_size=4)
    with pytest.raises(ValueError, match="divide"):
        allreduce_comm_plan(grads, comm_topology="hierarchical",
                            ici_size=3, world=8)
    with pytest.raises(ValueError, match="no inner level"):
        allreduce_comm_plan(grads, allreduce_compress_bf16=True)
    # auto: flat for 1 process, hierarchical across processes
    (a1,) = allreduce_comm_plan(grads, comm_topology="auto", nproc=1)
    assert a1["topology"] == "flat"
    (a2,) = allreduce_comm_plan(grads, comm_topology="auto", nproc=2,
                                world=8)
    assert a2["topology"] == "hierarchical" and a2["ici_size"] == 4


def test_comm_plan_matches_traced_buckets():
    """allreduce_comm_plan is the static twin of the traced bucketing:
    per-dtype buckets, chunk padding and wire bytes line up with what
    allreduce_grads_tree records at trace time."""
    from apex_tpu.parallel import allreduce_comm_plan
    grads = {"a": jnp.zeros((3000,)), "b": jnp.zeros((5000,)),
             "c": jnp.zeros((100,), jnp.bfloat16)}
    plan = allreduce_comm_plan(grads, message_size=4096)
    by_dtype = {b["dtype"]: b for b in plan}
    f32 = by_dtype["float32"]
    assert (f32["elements"], f32["chunks"], f32["cause"]) == \
        (8000, 2, "chunked")
    assert f32["wire_bytes"] == 2 * 4096 * 4
    bf16 = by_dtype["bfloat16"]
    assert (bf16["elements"], bf16["chunks"], bf16["cause"]) == \
        (100, 1, "single")
    assert bf16["wire_bytes"] == 200
    # the plan mirrors the runtime's unknown-trigger-path rejection: a
    # plan for a comm pattern the real step refuses to trace is no plan
    with pytest.raises(ValueError, match="not found"):
        allreduce_comm_plan(grads, trigger_paths={"nope/typo"})


# -- sharding rule (spec consistency + replication budget) ----------------

def _sharded_trace(fn, in_specs, out_specs, shape=(1024,), world=8):
    mesh = Mesh(np.array(jax.devices()[:world]), ("data",))
    mapped = jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
    return lambda: jax.make_jaxpr(mapped)(jnp.ones(shape))


def test_sharding_rule_flags_divergent_output_claim_both_ways():
    """check_vma=False (how every train entry point runs) means NOTHING
    at runtime verifies a replicated out-spec over a still-varying
    value — one replica's answer silently wins.  The propagator must
    flag the claim; the declared count must ratchet both directions."""
    varying = _sharded_trace(lambda x: x * 2.0, (P("data"),), P())

    over = _ep("mutant_divergent_out",
               expect={"sharding": {"mesh_axes": {"data": 8},
                                    "divergent_outputs": 0}},
               trace=varying)
    found = _run(over, "sharding")
    assert len(found) == 1
    assert "more agreement than the propagated" in found[0].message
    assert (found[0].detail["divergent"],
            found[0].detail["declared"]) == (1, 0)

    # the honest declaration (the non-synced BatchNorm-stats class)
    declared = _ep("fixed_divergent_out",
                   expect={"sharding": {"mesh_axes": {"data": 8},
                                        "divergent_outputs": 1}},
                   trace=varying)
    assert _run(declared, "sharding") == []

    # ...and a stale over-declaration must ratchet DOWN, not linger
    synced = _sharded_trace(lambda x: jax.lax.psum(x, "data"),
                            (P("data"),), P())
    stale = _ep("mutant_stale_declaration",
                expect={"sharding": {"mesh_axes": {"data": 8},
                                     "divergent_outputs": 1}},
                trace=synced)
    found = _run(stale, "sharding")
    assert len(found) == 1
    assert "ratchet divergent_outputs down" in found[0].message


def test_sharding_rule_flags_mesh_mismatch_and_vacuity():
    trace = _sharded_trace(lambda x: jax.lax.psum(x, "data"),
                           (P("data"),), P())
    wrong_mesh = _ep("mutant_wrong_mesh",
                     expect={"sharding": {"mesh_axes": {"data": 4},
                                          "divergent_outputs": 0}},
                     trace=trace)
    found = _run(wrong_mesh, "sharding")
    assert found and any("mesh" in f.message for f in found)

    # an expectation over a shard_map-free graph cannot pass silently
    vacuous = _ep("mutant_shardless",
                  expect={"sharding": {"mesh_axes": {"data": 8}}},
                  trace=lambda: jax.make_jaxpr(lambda x: x * 2.0)(
                      jnp.ones((8,))))
    found = _run(vacuous, "sharding")
    assert len(found) == 1 and "no shard_map" in found[0].message


def test_sharding_rule_flags_over_budget_replication():
    """The ZeRO ratchet: declare max_replicated_bytes below what the
    graph actually replicates and the ledger must flag, naming the
    largest contributor — the number a ZeRO-2 shard of optimizer state
    is supposed to shrink."""
    # replicated (P()) operand of 4 KB on the 8-way mesh: 7 duplicate
    # copies = 28672 world-total duplicate bytes
    trace = _sharded_trace(lambda x: jax.lax.psum(x, "data"),
                           (P(),), P())
    over = _ep("mutant_replication_budget",
               expect={"sharding": {"mesh_axes": {"data": 8},
                                    "divergent_outputs": 0,
                                    "max_replicated_bytes": 1000}},
               trace=trace)
    found = _run(over, "sharding")
    assert len(found) == 1
    assert found[0].detail["replicated_bytes"] == 7 * 1024 * 4
    assert "largest contributor" in found[0].message

    within = _ep("fixed_replication_budget",
                 expect={"sharding": {"mesh_axes": {"data": 8},
                                      "divergent_outputs": 0,
                                      "max_replicated_bytes":
                                      7 * 1024 * 4}},
                 trace=trace)
    assert _run(within, "sharding") == []


# -- resharding-census rule -----------------------------------------------

def test_resharding_census_flags_unplanned_all_gather():
    """The tentpole's seeded mutation: a full all-gather smuggled in
    AFTER the honest hierarchical chain.  The psum census is identical
    to the planned graph — only matching each placement-changing eqn
    against the comm plan's per-eqn payload list catches it, and the
    finding must name the operand."""
    from apex_tpu import parallel
    mesh, ici_groups, dcn_groups = _hier_setup()
    n = 1024

    def honest(x):
        return parallel.allreduce_grads_tree(
            {"w": x}, "data", comm_topology="hierarchical", ici_size=4,
            gradient_average=False)["w"]

    def sneaky(x):
        y = honest(x)
        # the smuggled reshard: "XLA silently replicated my shard"
        g = jax.lax.all_gather(y, "data", tiled=True)
        return y + g[:n]

    plan = parallel.allreduce_comm_plan(
        {"w": jnp.zeros((n,), jnp.float32)},
        comm_topology="hierarchical", ici_size=4, world=8)
    expect = parallel.plan_resharding_expectations(plan)

    def _trace(fn):
        mapped = jax.shard_map(fn, mesh=mesh, in_specs=(P(),),
                               out_specs=P(), check_vma=False)
        return lambda: jax.make_jaxpr(mapped)(jnp.ones((n,)))

    broken = _ep("mutant_unplanned_gather",
                 expect={"resharding": dict(expect)},
                 trace=_trace(sneaky))
    found = _run(broken, "resharding-census")
    assert len(found) == 1, found
    assert found[0].detail["primitive"] == "all_gather"
    assert "unplanned" in found[0].message
    assert found[0].detail["payload_bytes"] == n * 4

    fixed = _ep("fixed_planned_chain",
                expect={"resharding": dict(expect)},
                trace=_trace(honest))
    assert _run(fixed, "resharding-census") == []

    # a declared budget absorbs exactly that many unplanned eqns --
    # the paved path for an intentionally-unplanned reshard
    budgeted = _ep("fixed_budgeted_gather",
                   expect={"resharding": dict(
                       expect, budget={"all_gather": 1})},
                   trace=_trace(sneaky))
    assert _run(budgeted, "resharding-census") == []


def test_resharding_census_flags_plan_graph_desync():
    """The other direction: the plan schedules a chain the graph never
    issues (flat allreduce traced under hierarchical expectations) —
    a plan/graph desync, not a silent pass."""
    from apex_tpu import parallel
    mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
    n = 1024

    plan = parallel.allreduce_comm_plan(
        {"w": jnp.zeros((n,), jnp.float32)},
        comm_topology="hierarchical", ici_size=4, world=8)
    expect = parallel.plan_resharding_expectations(plan)

    flat = jax.shard_map(lambda x: jax.lax.psum(x, "data"), mesh=mesh,
                         in_specs=(P(),), out_specs=P(),
                         check_vma=False)
    broken = _ep("mutant_plan_desync",
                 expect={"resharding": dict(expect)},
                 trace=lambda: jax.make_jaxpr(flat)(jnp.ones((n,))))
    found = _run(broken, "resharding-census")
    assert found and all("never issues" in f.message for f in found)
    assert {f.detail["primitive"] for f in found} == \
        {"reduce_scatter", "all_gather"}

    # vacuity: a resharding expectation over a shard_map-free graph
    vacuous = _ep("mutant_resharding_shardless",
                  expect={"resharding": dict(expect)},
                  trace=lambda: jax.make_jaxpr(lambda x: x + 1.0)(
                      jnp.ones((4,))))
    found = _run(vacuous, "resharding-census")
    assert len(found) == 1 and "no shard_map" in found[0].message


# -- the replication ledger over real entry points ------------------------

def test_sharding_ledger_reports_replicated_optimizer_state():
    """The acceptance number: on the ZeRO-1 DDP train step the ledger
    must statically report the fp32 master/optimizer state as fully
    replicated (factor 8 on the 8-way mesh, ~7/8 of world bytes
    duplicated), and its argument accounting must agree byte-for-byte
    with the memory plane's jaxpr walk — same graph, two lenses."""
    from apex_tpu.observability import memory as obsmem
    ep = analysis.get("ddp_resnet18_o2")
    rec = analysis.entry_point_sharding_record(ep)
    assert rec["kind"] == "sharding" and rec["world"] == 8
    assert rec["mesh_axes"] == {"data": 8}

    # cross-check against the memory plane on the same jaxpr
    live = obsmem.jaxpr_live_bytes(ep.graph().jaxpr)
    assert rec["argument_bytes"] == live["argument_bytes"]
    # the ledger identity: every byte is unique or duplicate
    assert rec["unique_bytes"] + rec["replicated_bytes"] == \
        rec["world"] * rec["argument_bytes"]

    # ZeRO-1 DDP: params + fp32 master + both Adam moments all ride
    # every rank -- factor 8, and fp32 dominates the duplicate bytes
    assert rec["replicated_fraction"] > 0.80
    f32 = rec["replicated_bytes_by_dtype"]["float32"]
    assert f32 > 0.8 * rec["replicated_bytes"]
    assert rec["top_replicated"], "ledger must name the arrays"
    for t in rec["top_replicated"]:
        assert t["replication_factor"] == 8
        assert t["spec"] == "replicated"
    # fp32 master + m + v: three full fp32 copies of the parameters
    # (~2.6x the mixed-precision compute params) — for resnet18 that
    # is ~0.94 GB of world-total duplicate fp32 under ZeRO-1
    assert 0.8e9 < f32 < 1.1e9


def test_sharding_ledger_zero2_sharded_state_is_not_replicated():
    """The contrast the ledger exists to draw: shard the same bytes
    with a spec that actually partitions ('data',) and the duplicate
    count drops to zero — the ZeRO-2/3 direction ROADMAP item 2 will
    ratchet with max_replicated_bytes."""
    repl = _ep("ledger_replicated",
               trace=_sharded_trace(lambda x: jax.lax.psum(x, "data"),
                                    (P(),), P()))
    shard = _ep("ledger_sharded",
                trace=_sharded_trace(lambda x: jax.lax.psum(x, "data"),
                                     (P("data"),), P()))
    r = analysis.entry_point_sharding_record(repl)
    s = analysis.entry_point_sharding_record(shard)
    assert r["replicated_bytes"] == 7 * 1024 * 4
    assert r["replicated_fraction"] == pytest.approx(7 / 8)
    assert s["replicated_bytes"] == 0
    assert s["unique_bytes"] == 8 * s["argument_bytes"]

    # a shard_map-free entry point raises the bare-RuntimeError skip
    # class the CLI uses to exempt single-device graphs
    bare = _ep("ledger_no_shardmap",
               trace=lambda: jax.make_jaxpr(lambda x: x + 1.0)(
                   jnp.ones((4,))))
    with pytest.raises(RuntimeError, match="no shard_map") as ei:
        analysis.entry_point_sharding_record(bare)
    assert type(ei.value) is RuntimeError


def test_sharding_rule_ratchet_flags_stale_budget_both_ways():
    """The ratchet-down direction (RATCHET_FRACTION): a ZeRO stage
    collapses the replicated state but the declared budget stays at
    the pre-ZeRO value — with >25% headroom the ledger must flag the
    stale declaration (else a regression back to full replication
    would still 'pass'), while a snug budget at measured/0.75 does
    not."""
    trace = _sharded_trace(lambda x: jax.lax.psum(x, "data"),
                           (P(),), P())
    measured = 7 * 1024 * 4                       # world-total dupes
    stale = _ep("mutant_stale_replication_budget",
                expect={"sharding": {"mesh_axes": {"data": 8},
                                     "divergent_outputs": 0,
                                     "max_replicated_bytes":
                                     measured * 2}},
                trace=trace)
    found = _run(stale, "sharding")
    assert len(found) == 1, found
    assert "stale" in found[0].message
    assert found[0].detail["replicated_bytes"] == measured
    assert found[0].detail["budget_bytes"] == measured * 2

    snug = _ep("fixed_snug_replication_budget",
               expect={"sharding": {"mesh_axes": {"data": 8},
                                    "divergent_outputs": 0,
                                    "max_replicated_bytes":
                                    int(measured / 0.75)}},
               trace=trace)
    assert _run(snug, "sharding") == []


def test_sharding_ledger_zero3_collapses_replicated_fraction():
    """The tentpole acceptance pin: all four ZeRO entry points are
    registered, and the stage-3 step's replication ledger collapses —
    the fp32 master/moment state that rides every rank under plain DDP
    (fraction > 0.8) becomes the parameter store's ICI shard, leaving
    only BN state, scaler scalars and gather tables replicated
    (fraction < 0.01, within the declared ratchet budget).  Records
    carry the ``zero_stage`` stamp the exporters require."""
    for name in ("ddp_resnet18_o2_zero1", "ddp_resnet18_o2_zero2",
                 "ddp_resnet18_o2_zero3", "ddp_mlp_overlap_zero2"):
        assert name in analysis.ENTRY_POINTS
    assert len(analysis.ENTRY_POINTS) >= 29

    base = analysis.entry_point_sharding_record(
        analysis.get("ddp_resnet18_o2"))
    z3 = analysis.entry_point_sharding_record(
        analysis.get("ddp_resnet18_o2_zero3"))
    assert base["replicated_fraction"] > 0.80
    assert z3["replicated_fraction"] < 0.01
    assert z3["replicated_bytes"] <= 1_333_000    # the declared ratchet
    assert z3["zero_stage"] == 3
    assert "zero_stage" not in base
    assert exporters.validate_sharding_record(
        exporters.JsonlExporter.enrich(z3)) == []


def test_zero2_overlap_interleaving_mutation_both_ways():
    """The tentpole's fused-schedule position pin, mutation-proofed:
    the SAME fused ZeRO-2 staged step traced with overlap=False
    (identical census, payloads and fabric levels — the whole
    scatter/update/gather chain just runs after the full backward)
    must flag the ``min_collectives_before_last_matmul`` floor derived
    from ``overlap_comm_schedule(zero_stage=2)``, and the overlapped
    trace must lint clean under the same expectations."""
    from apex_tpu import parallel
    from jax import lax
    ici, stages, hidden, B = 4, 4, 32, 8
    ndev = len(jax.devices())
    rng = np.random.RandomState(20)
    stage_params = [
        {"w": jnp.asarray(rng.randn(hidden, hidden) * 0.1, jnp.float32),
         "b": jnp.zeros((hidden,), jnp.float32)}
        for _ in range(stages)]
    x = jnp.asarray(rng.randn(B, hidden), jnp.float32)
    y = jnp.asarray(rng.randn(B, hidden), jnp.float32)
    stage_fns = [lambda p, a: jnp.tanh(a @ p["w"] + p["b"])] * stages
    mesh = Mesh(np.array(jax.devices()), ("data",))

    def graph_with(overlap):
        ddp = parallel.DistributedDataParallel(
            comm_topology="hierarchical", ici_size=ici,
            overlap=overlap, zero_stage=2)

        def step(params_list, batch):
            xb, yb = batch
            loss, new = ddp.staged_zero2_allreduce_grads(
                stage_fns, lambda a: jnp.mean((a - yb) ** 2),
                params_list, xb,
                lambda stage, p_sh, g_sh: p_sh - 0.1 * g_sh)
            return new, lax.pmean(loss, "data")

        mapped = jax.shard_map(step, mesh=mesh,
                               in_specs=(P(), (P("data"), P("data"))),
                               out_specs=(P(), P()), check_vma=False)
        return lambda: jax.make_jaxpr(mapped)(stage_params, (x, y))

    schedule = parallel.overlap_comm_schedule(
        stage_params, comm_topology="hierarchical", ici_size=ici,
        world=ndev, nproc=1, overlap=True, zero_stage=2)
    expect = {"collectives": parallel.overlap_collective_expectations(
        schedule, extra_psums=2, extra_psum_bytes=2 * 4)}
    assert expect["collectives"]["interleaving"][
        "min_collectives_before_last_matmul"] > 0

    broken = _ep("mutant_zero2_reduce_after_backward",
                 expect=dict(expect), trace=graph_with(False))
    found = _run(broken, "collective")
    assert len(found) == 1, found
    assert "reduce-after-backward schedule" in found[0].message
    assert found[0].detail["first_collective_eqn"] > \
        found[0].detail["last_matmul_eqn"]

    fixed = _ep("fixed_zero2_overlapped",
                expect=dict(expect), trace=graph_with(True))
    assert _run(fixed, "collective") == []


# -- findings as JSONL: schema + exporters integration --------------------

def _enriched(finding):
    return exporters.JsonlExporter.enrich(finding.to_record())


def test_lint_record_schema_roundtrip():
    f = analysis.Finding(rule="donation", entry_point="engine_step_k",
                         message="cache not aliased",
                         detail={"argument": "cache"})
    rec = _enriched(f)
    assert exporters.validate_lint_record(rec) == []
    assert rec["kind"] == "graph_lint"
    assert rec["schema_version"] == exporters.SCHEMA_VERSION
    assert rec["stale"] is False

    bad = dict(rec)
    bad["severity"] = "catastrophic"
    assert any("severity" in e
               for e in exporters.validate_lint_record(bad))
    missing = {k: v for k, v in rec.items() if k != "rule"}
    assert any("rule" in e
               for e in exporters.validate_lint_record(missing))


def test_lint_summary_schema():
    good = exporters.JsonlExporter.enrich(
        {"kind": "graph_lint_summary", "entry_points": 13, "rules": 5,
         "findings": 2, "errors": 1, "warnings": 1})
    assert exporters.validate_lint_record(good) == []
    bad = dict(good, findings=3)
    assert any("errors" in e for e in exporters.validate_lint_record(bad))


def test_telemetry_jsonl_validates_mixed_stream():
    """One stream may interleave lint findings, fleet snapshots and
    request traces; the dispatching validator checks each against its
    own schema, and a line without a known ``kind`` against none."""
    import json
    lint_rec = _enriched(analysis.Finding(
        rule="layout", entry_point="x", message="leak"))
    fleet_rec = exporters.JsonlExporter.enrich(
        {"kind": "fleet", "trace_id": "fleet-1f-1",
         "replicas": 2, "policy": "least_loaded",
         "healthy": 1, "degraded": 0, "dead": 1, "queue_depth": 0,
         "submitted": 8, "finished": 8, "failed": 0, "shed": 0,
         "retries": 1, "failovers": 3, "drains": 0, "tokens": 64,
         # the per-tenant rollup, required
         "tenants": {}, "tenants_dropped": 0,
         # the per-QoS-class rollup, required
         "classes": {}, "preemptions": 0})
    trace_rec = exporters.JsonlExporter.enrich(
        {"kind": "trace", "trace_id": "fleet-1f-1/r0", "span_count": 2,
         "spans": [{"name": "fleet_submit", "ph": "i", "ts": 1.0,
                    "span_id": 1, "trace_id": "fleet-1f-1/r0"},
                   {"name": "fleet_result", "ph": "i", "ts": 9.0,
                    "span_id": 2, "parent_id": 1,
                    "trace_id": "fleet-1f-1/r0"}]})
    lines = [json.dumps(lint_rec), json.dumps(fleet_rec),
             json.dumps(trace_rec)]
    assert exporters.validate_telemetry_jsonl(lines) == []
    # a trace violation is kind-dispatched and caught positionally
    trace_bad = dict(trace_rec, span_count=9)
    errs = exporters.validate_telemetry_jsonl(
        [json.dumps(lint_rec), json.dumps(trace_bad)])
    assert len(errs) == 1 and "line 2" in errs[0] \
        and "span_count" in errs[0]
    # a lint violation is caught positionally
    lint_rec2 = dict(lint_rec, message="")
    lines = [json.dumps(trace_rec), json.dumps(lint_rec2),
             json.dumps(fleet_rec)]
    errs = exporters.validate_telemetry_jsonl(lines)
    assert len(errs) == 1 and "line 2" in errs[0]
    # a fleet violation too
    fleet_bad = dict(fleet_rec, failovers=-1)
    errs = exporters.validate_telemetry_jsonl(
        [json.dumps(lint_rec), json.dumps(fleet_bad)])
    assert len(errs) == 1 and "line 2" in errs[0] \
        and "failovers" in errs[0]
    # a line of no known kind has no schema to fall back on: a metric
    # line without ``kind``, and a misspelt kind
    for unknown in (exporters.JsonlExporter.enrich(
                        {"metric": "engine_decode", "value": 100.0,
                         "unit": "tokens/sec"}),
                    dict(fleet_rec, kind="fleets")):
        errs = exporters.validate_telemetry_jsonl(
            [json.dumps(lint_rec), json.dumps(unknown)])
        assert len(errs) == 1 and "line 2" in errs[0] \
            and "'kind'" in errs[0]


def test_memory_record_schema_and_dispatch():
    """``kind: memory`` record contract (satellite): required analytic
    + plan fields, the peak_bytes reassembly cross-check, and the
    telemetry dispatcher routing it by kind."""
    good = exporters.JsonlExporter.enrich({
        "kind": "memory", "entry_point": "engine_step_k",
        "source": "compiled", "flops": 1.5e6, "transcendentals": 100.0,
        "matmul_flops": 1.4e6, "bytes_accessed": 2_000_000,
        "argument_bytes": 1000, "output_bytes": 1000,
        "temp_bytes": 500, "alias_bytes": 900,
        "generated_code_bytes": 0, "peak_bytes": 1600,
        "analytic_live_bytes": 1400})
    assert exporters.validate_memory_record(good) == []
    # kind-dispatched
    assert exporters.validate_telemetry_record(good) == []
    # arithmetic cross-check: a peak that doesn't reassemble flags
    assert any("peak_bytes" in e for e in
               exporters.validate_memory_record(
                   dict(good, peak_bytes=9999)))
    # a subject is required
    assert any("entry_point" in e for e in
               exporters.validate_memory_record(
                   {k: v for k, v in good.items()
                    if k != "entry_point"}))
    assert any("flops" in e for e in
               exporters.validate_memory_record(
                   {k: v for k, v in good.items() if k != "flops"}))
    assert any("temp_bytes" in e for e in
               exporters.validate_memory_record(
                   dict(good, temp_bytes=-1)))
    # positionally caught in a mixed stream
    import json
    errs = exporters.validate_telemetry_jsonl(
        [json.dumps(good), json.dumps(dict(good, peak_bytes=9999))])
    assert len(errs) == 1 and "line 2" in errs[0]


def test_sharding_record_schema_and_dispatch():
    """``kind: sharding`` record contract: the ledger
    identity must reassemble, the fraction must be consistent, and the
    telemetry dispatcher routes it by kind."""
    import json
    good = exporters.JsonlExporter.enrich({
        "kind": "sharding", "entry_point": "ddp_x", "source": "jaxpr",
        "world": 8, "mesh_axes": {"data": 8}, "shard_maps": 1,
        "argument_bytes": 1000, "unique_bytes": 1000,
        "replicated_bytes": 7000,
        "replicated_bytes_by_dtype": {"float32": 7000},
        "replicated_fraction": 0.875,
        "top_replicated": [{"index": 0, "shape": [250],
                            "dtype": "float32", "local_bytes": 1000,
                            "replication_factor": 8, "spec": "P()"}],
        "resharding_eqns": {}})
    assert exporters.validate_sharding_record(good) == []
    # kind-dispatched
    assert exporters.validate_telemetry_record(good) == []
    # the ledger identity: unique + replicated == world x argument
    assert any("reassemble" in e for e in
               exporters.validate_sharding_record(
                   dict(good, unique_bytes=900)))
    # the fraction must agree with its own numerator/denominator
    assert any("replicated_fraction" in e for e in
               exporters.validate_sharding_record(
                   dict(good, replicated_fraction=0.5)))
    # mesh must multiply out to the world
    assert any("mesh_axes" in e for e in
               exporters.validate_sharding_record(
                   dict(good, mesh_axes={"data": 4})))
    # per-dtype split must sum to the total
    assert any("replicated_bytes_by_dtype" in e for e in
               exporters.validate_sharding_record(
                   dict(good,
                        replicated_bytes_by_dtype={"float32": 1})))
    # positionally caught in a mixed stream next to a lint record
    lint = _enriched(analysis.Finding(
        rule="layout", entry_point="x", message="leak"))
    errs = exporters.validate_telemetry_jsonl(
        [json.dumps(lint), json.dumps(dict(good, world=0))])
    assert len(errs) >= 1 and all("line 2" in e for e in errs)


def test_findings_to_records_and_registry_surface():
    assert set(analysis.RULES) == {"host-transfer", "donation",
                                   "amp-dtype", "layout", "collective",
                                   "flop-accounting", "memory-budget",
                                   "numerics", "supervisor",
                                   "sharding", "resharding-census"}
    for name in ("ddp_resnet18_o2", "engine_step_k", "seq2seq_step_k",
                 "tp_mlp_train_step", "ddp_resnet18_o2_numerics",
                 "ddp_resnet18_o2_numerics_off",
                 "ddp_resnet18_o2_supervised",
                 "ddp_resnet18_o2_supervised_off"):
        assert name in analysis.ENTRY_POINTS
    f = analysis.Finding(rule="r", entry_point="e", message="m")
    (rec,) = analysis.findings_to_records([f])
    assert rec == {"kind": "graph_lint", "rule": "r", "severity": "error",
                   "entry_point": "e", "message": "m"}


def test_entry_point_build_restores_global_policy():
    """amp.initialize(O1) installs a process-wide cast policy and
    nothing uninstalls it; EntryPoint.graph() must restore the global
    after every build, or the O1 entry point silently re-dtypes every
    graph built after it in the same process (the CLI has no conftest
    _reset_amp_policy to hide behind — this leak shifted the TP entry
    point's psum payload from fp32 to bf16 when first caught)."""
    from apex_tpu import amp, models, optimizers
    from apex_tpu.amp import policy as P
    name = "mutant_policy_leak"

    def build(ep):
        amp.initialize(models.resnet18(num_classes=10),
                       optimizers.FusedAdam(1e-3), opt_level="O1",
                       verbosity=0)
        assert not isinstance(P.current_policy(), P.NoPolicy)
        return Graph(trace=lambda: None)

    analysis.register_entry_point(name)(build)
    try:
        before = P.current_policy()
        analysis.get(name).graph()
        assert P.current_policy() is before
    finally:
        del analysis.ENTRY_POINTS[name]


# -- CLI ------------------------------------------------------------------

def test_cli_list_and_single_entry_point(capsys):
    from apex_tpu.analysis.__main__ import main
    assert main(["--list"]) == 0
    out = capsys.readouterr().out
    assert "engine_step_k" in out and "rules:" in out

    # lint one cheap entry point end to end: stdout must be pure
    # schema-valid JSONL ending in a summary record
    assert main(["--entry-points", "engine_prefill_slot"]) == 0
    out = capsys.readouterr().out
    assert exporters.validate_telemetry_jsonl(out.splitlines()) == []
    import json
    last = json.loads(out.strip().splitlines()[-1])
    assert last["kind"] == "graph_lint_summary"
    assert last["errors"] == 0


def test_cli_memory_flag(capsys):
    """`python -m apex_tpu.analysis --memory` (satellite): pure
    schema-valid JSONL, one ``kind: memory`` record per entry point,
    analytic FLOPs + the compiled plan side by side."""
    from apex_tpu.analysis.__main__ import main
    assert main(["--memory",
                 "--entry-points", "engine_prefill_slot"]) == 0
    out = capsys.readouterr().out
    assert exporters.validate_telemetry_jsonl(out.splitlines()) == []
    import json
    (rec,) = [json.loads(ln) for ln in out.strip().splitlines()]
    assert rec["kind"] == "memory"
    assert rec["entry_point"] == "engine_prefill_slot"
    assert rec["flops"] > 0 and rec["peak_bytes"] > 0
    assert rec["alias_bytes"] > 0             # donation plan visible


def test_cli_entry_and_rule_filters(capsys):
    """`--entry`/`--rule` substring filters (satellite): --list honors
    both, a filtered run emits schema-valid JSONL with the filtered
    rule set only, and an unmatched filter exits 2 like any other
    selection error."""
    import json
    from apex_tpu.analysis.__main__ import main
    assert main(["--list", "--entry", "engine", "--rule", "shard"]) == 0
    out = capsys.readouterr().out
    assert "engine_step_k" in out and "ddp_resnet18_o2" not in out
    rules_line = [ln for ln in out.splitlines()
                  if ln.startswith("rules:")][0]
    assert rules_line == "rules: resharding-census, sharding"

    # a filtered run is still pure schema-valid JSONL with the usual
    # summary envelope, now over the narrowed cross product
    assert main(["--entry", "engine_prefill", "--rule", "donat"]) == 0
    out = capsys.readouterr().out
    assert exporters.validate_telemetry_jsonl(out.splitlines()) == []
    last = json.loads(out.strip().splitlines()[-1])
    assert last["kind"] == "graph_lint_summary"
    assert (last["entry_points"], last["rules"]) == (1, 1)

    assert main(["--entry", "zzz_no_such"]) == 2
    assert main(["--rule", "zzz_no_such"]) == 2


def test_cli_sharding_flag(capsys):
    """`python -m apex_tpu.analysis --sharding`: one `kind: sharding`
    record per entry point, schema-valid, serving engines
    skipped via the bare-RuntimeError gate rather than failing."""
    import json
    from apex_tpu.analysis.__main__ import main
    assert main(["--sharding", "--entry", "ddp_mlp_overlap_flat"]) == 0
    out = capsys.readouterr().out
    assert exporters.validate_telemetry_jsonl(out.splitlines()) == []
    (rec,) = [json.loads(ln) for ln in out.strip().splitlines()]
    assert rec["kind"] == "sharding"
    assert rec["schema_version"] == exporters.SCHEMA_VERSION
    assert rec["entry_point"] == "ddp_mlp_overlap_flat"
    assert rec["world"] == 8 and rec["replicated_bytes"] > 0

    # a shard_map-free serving engine is a skip, not a failure
    assert main(["--sharding", "--entry", "engine_prefill_slot"]) == 0
    captured = capsys.readouterr()
    assert captured.out.strip() == ""
    assert "skipped" in captured.err


def test_cli_exit_nonzero_on_finding(monkeypatch):
    """The CI gate contract: any error finding => exit 1.  Register a
    throwaway broken entry point, lint only it, then clean up."""
    from apex_tpu.analysis.__main__ import main
    name = "mutant_cli_host_sync"

    def build(ep):
        def f(x):
            return jax.pure_callback(
                lambda a: np.asarray(a),
                jax.ShapeDtypeStruct(x.shape, x.dtype), x)
        return Graph(trace=lambda: jax.make_jaxpr(f)(jnp.ones(4)))

    analysis.register_entry_point(name)(build)
    try:
        assert main(["--entry-points", name,
                     "--rules", "host-transfer"]) == 1
    finally:
        del analysis.ENTRY_POINTS[name]
