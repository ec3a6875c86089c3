"""Static precondition lint over the real Pallas kernel family, plus
mutation coverage for every check class.

The positive direction traces every public kernel wrapper in
``ops/pallas_*.py`` under the ``pallas_call`` recorder and asserts the
whole family lints clean; the negative direction hand-builds sites with
a non-divisible block, an out-of-bounds index map, a double-aliased
output, and a shape-mismatched donation, and asserts each one is
flagged — so the lint can neither rot into vacuity nor pass a broken
kernel.
"""

from types import SimpleNamespace

import pytest

from apex_tpu.analysis import pallas_lint
from apex_tpu.analysis.pallas_lint import KernelSite, check_site


def _spec(block_shape, index_map=None):
    if index_map is None and block_shape is not None:
        index_map = lambda *idx: idx if len(idx) > 1 else (idx[0],) * \
            len(block_shape)
    return SimpleNamespace(block_shape=block_shape, index_map=index_map)


def _site(**kw):
    base = dict(
        name="mutant",
        grid=(4,),
        in_specs=[_spec((512, 128), lambda i: (i, 0))],
        out_specs=[_spec((512, 128), lambda i: (i, 0))],
        in_shapes=[((2048, 128), "float32")],
        out_shapes=[((2048, 128), "float32")],
        input_output_aliases={0: 0},
    )
    base.update(kw)
    return KernelSite(**base)


def test_clean_site_passes():
    assert check_site(_site()) == []


def test_non_divisible_block_flags():
    """Dropping the pad (2048 -> 2000 rows under 512-row blocks) must
    flag: partial tiles are exactly what to_2d's padding prevents."""
    bad = _site(in_shapes=[((2000, 128), "float32")],
                out_shapes=[((2000, 128), "float32")])
    problems = check_site(bad)
    assert any("not divisible" in p for p in problems), problems


def test_a_ragged_dim_is_a_fault_only_where_a_step_reaches_its_partial_block():
    """Column blocks 0-2 of an array 448 wide leave its last 64 columns
    alone; a fourth step would read the partial block."""
    cols = lambda n: _site(
        grid=(n,), in_specs=[_spec((8, 128), lambda j: (0, j))],
        in_shapes=[((8, 448), "float32")],
        out_specs=[_spec((8, 128), lambda j: (0, j))],
        out_shapes=[((8, 128 * n), "float32")], input_output_aliases={})
    assert check_site(cols(3)) == []
    assert any("not divisible" in p for p in check_site(cols(4)))


def test_out_of_bounds_index_map_flags():
    """An off-by-one index map (i+1) steps past the last block at the
    top grid corner."""
    bad = _site(in_specs=[_spec((512, 128), lambda i: (i + 1, 0))],
                input_output_aliases={})
    problems = check_site(bad)
    assert any("out of [0, 4)" in p for p in problems), problems


def test_index_map_rank_mismatch_flags():
    bad = _site(in_specs=[_spec((512, 128), lambda i: (i,))],
                input_output_aliases={})
    problems = check_site(bad)
    assert any("returns 1 indices for a rank-2 block" in p
               for p in problems), problems


def test_double_aliased_output_flags():
    """Two inputs donated onto one output is two refs racing one
    buffer — must be declared exactly once."""
    bad = _site(
        in_specs=[_spec((512, 128), lambda i: (i, 0))] * 2,
        in_shapes=[((2048, 128), "float32")] * 2,
        input_output_aliases={0: 0, 1: 0})
    problems = check_site(bad)
    assert any("aliased twice" in p for p in problems), problems


def test_alias_shape_mismatch_flags():
    bad = _site(out_shapes=[((2048, 128), "bfloat16")])
    problems = check_site(bad)
    assert any("shape/dtype mismatch" in p for p in problems), problems


def test_alias_index_out_of_range_flags():
    bad = _site(input_output_aliases={3: 0})
    problems = check_site(bad)
    assert any("out of range" in p for p in problems), problems


def test_smem_scalar_spec_is_exempt():
    """Scalar-prefetch/SMEM specs carry block_shape=None; nothing is
    blocked, so nothing to check."""
    site = _site(in_specs=[SimpleNamespace(block_shape=None,
                                           index_map=None)],
                 in_shapes=[((2,), "int32")],
                 input_output_aliases={})
    assert check_site(site) == []


# -- the real kernel family ----------------------------------------------

def test_real_kernel_family_lints_clean():
    """Every pallas_call the ops package launches — Adam (both
    write-out arities), LAMB stages, layer-norm fwd/bwd, the
    multi-tensor family, flash attention
    fwd/dq/dkv on head-major and on token-major, grouped operands, the
    rotary pass, the selective scan's forward and backward, and the
    short convolution's pair in both forms — satisfies the
    block/index/alias preconditions."""
    sites, problems = pallas_lint.lint_pallas_kernels()
    assert problems == []
    names = {s.name for s in sites}
    # the sweep must actually reach each kernel family; a refactor
    # that silently stops launching is as much a failure as a bad spec
    for expected in ("_adam_kernel", "_stage1_kernel", "_stage2_kernel",
                     "_scale_kernel", "_axpby_kernel", "_l2norm_kernel",
                     "_dq_kernel", "_dkv_kernel", "_kernel", "_rows_kernel",
                     "_stack_kernel", "_fwd_kernel", "_bwd_kernel",
                     "_conv_fwd", "_gated_bwd", "_silu_bwd"):
        assert expected in names, (expected, sorted(names))
    # the short convolution: three blocks of tokens a sequence; the gated
    # backward's last axis walks the cotangent's three parts; the silu form
    # reads 128-lane column blocks 1-2 of a projection 448 wide, whose
    # ragged last block no step reaches
    conv = {s.name: s for s in sites if s.name in ("_gated_bwd", "_silu_bwd")}
    assert conv["_gated_bwd"].grid == (2, 1, 3, 3)
    assert conv["_silu_bwd"].grid == (1, 2, 3, 1)
    assert conv["_silu_bwd"].in_shapes[0][0][2] % 128 == 64
    assert conv["_silu_bwd"].in_specs[1].block_shape == (1, 16, 128)
    assert len(sites) >= 12, [s.describe() for s in sites]
    # token-major launches: a (1, blk, hb * D) block of a (B, T, H * D)
    # array, and dk/dv's sequential axis six times its sweep where six
    # query heads go one a step
    dkv = [s for s in sites if s.name == "_dkv_kernel"]
    assert [s.grid for s in dkv] == [(2, 1, 1), (1, 2, 18), (4, 2, 2), (2, 1, 1)]
    assert dkv[1].in_specs[0].block_shape == (1, 128, 128)
    assert dkv[2].in_specs[0].block_shape == (1, 256, 4 * 128)
    assert dkv[2].in_specs[1].block_shape == (1, 256, 128)
    # a score head with a rope part: the step's four heads' 64 each as two lane
    # tiles, the one shared key head as one, dk's rope part a float32 tile a step
    assert [spec.block_shape for spec in dkv[3].in_specs[6:8]] == [(1, 256, 256), (1, 256, 128)]
    assert dkv[3].out_shapes[2] == ((2, 256, 128), "float32")


def test_grouped_products_are_linted_with_their_work_items():
    """The grouped products' index maps look their blocks up in
    scalar-prefetch operands: the recorder keeps the values each launch
    was traced with (all rows in the first group, all in the last, a
    near-uniform split with a dead tail, no rows), the lint evaluates the
    maps with them at every grid point, and a launch is the forward, the
    rows' gradient or the stack's, three a split."""
    sites = [s for s in pallas_lint.collect_kernel_sites()
             if s.name in ("_rows_kernel", "_stack_kernel")]
    assert [s.name for s in sites] == ["_rows_kernel", "_rows_kernel",
                                       "_stack_kernel"] * 4
    for site in sites:
        offsets, group = site.scalar_prefetch[:2]
        assert len(site.scalar_prefetch) == (5 if site.name == "_rows_kernel" else 4)
        assert len(group) == site.grid[-1] == 512 // 128 + 4 - 1
        assert check_site(site) == []
    # all rows in the last group: every item reads the last weight block
    first, last = sites[0].scalar_prefetch, sites[3].scalar_prefetch
    assert set(first[1].tolist()) == {0} and set(last[1].tolist()) == {3}
    # the split with a dead tail: two tiles of products, two of zeros
    assert sites[6].scalar_prefetch[4].tolist().count(3) == 2


def test_a_scalar_prefetch_index_map_is_checked_between_the_corners():
    """A block looked up in a table is bounded by no corner: the table's
    one bad entry sits in the middle of the grid."""
    import numpy as np
    table = np.asarray([0, 1, 7, 3])
    spec = _spec((512, 128), lambda i, t: (t[i], 0))
    bad = _site(in_specs=[spec], out_specs=[spec], input_output_aliases={},
                scalar_prefetch=[table])
    problems = check_site(bad)
    assert any("grid point (2,)" in p and "block index 7" in p
               for p in problems), problems
    good = _site(in_specs=[spec], out_specs=[spec], input_output_aliases={},
                 scalar_prefetch=[np.asarray([0, 1, 2, 3])])
    assert check_site(good) == []


def test_a_scalar_prefetch_launch_traced_without_values_is_flagged_not_skipped():
    bad = _site(in_specs=[_spec((512, 128), lambda i, t: (t[i], 0))],
                input_output_aliases={}, scalar_prefetch=None)
    problems = check_site(bad)
    assert any("traced without values" in p for p in problems), problems


def test_aliased_kernels_record_their_donations():
    """The in-place optimizer kernels must show up with their aliases
    intact — the recorder sees the same dict pallas_call gets."""
    sites = pallas_lint.collect_kernel_sites()
    adam = [s for s in sites if s.name == "_adam_kernel"]
    assert adam and all(s.input_output_aliases == {1: 0, 2: 1, 3: 2}
                        for s in adam)
    stage2 = [s for s in sites if s.name == "_stage2_kernel"]
    assert stage2 and stage2[0].input_output_aliases == {1: 0}
