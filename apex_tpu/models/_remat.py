"""Per-block rematerialization for the transformer families.

The reference-era equivalent is torch checkpointing (not in the 2019
Apex snapshot); on TPU this is the standard HBM lever: activations are
the long-context memory bottleneck, and ``jax.checkpoint`` around each
decoder block trades backward-pass FLOPs for not storing them
(SURVEY.md §preamble: "use jax.checkpoint / rematerialisation to trade
FLOPs for memory").

Modes (the ``remat=`` config field on GPTConfig/LlamaConfig):

- ``None``        — store everything (XLA default).
- ``"nothing"``   — save only block boundaries and the flash attention
                    kernel's two results (``o``, one block boundary's
                    size, and its fp32 row statistics ``lse``);
                    everything else of the block is recomputed in
                    backward (max memory saving short of running the
                    kernel's forward twice).
- ``"dots"``      — ``dots_with_no_batch_dims_saveable`` and the same
                    two kernel results: keep matmul outputs, recompute
                    the cheap elementwise/norm ops — the usual sweet
                    spot on MXU-bound steps.

The kernel's results are kept by name (``FLASH_OUT_NAME``,
``FLASH_LSE_NAME``, given inside the kernel's forward rule): no policy
that looks at primitives can see a Mosaic call's result, so without the
names the backward of every block launched ``flash_fwd`` a second time
to get ``o`` and ``lse`` back.  A block with no flash call in it holds
no such name and keeps exactly what it kept.

Gradients are bit-identical either way (pinned in tests/test_remat.py,
along with a backward-FLOPs increase check and the launch count).
"""

from __future__ import annotations

import jax

from ..ops.pallas_flash_attention import FLASH_LSE_NAME, FLASH_OUT_NAME

__all__ = ["wrap_block"]

_MODES = (None, "nothing", "dots")


def wrap_block(fn, mode):
    """``fn(params, x) -> out`` wrapped per ``mode`` (see module doc)."""
    if mode is None:
        return fn
    policies = jax.checkpoint_policies
    policy = policies.save_only_these_names(FLASH_OUT_NAME, FLASH_LSE_NAME)
    if mode == "dots":
        policy = policies.save_from_both_policies(
            policies.dots_with_no_batch_dims_saveable, policy)
    elif mode != "nothing":
        raise ValueError(f"remat mode {mode!r} not in {_MODES}")
    return jax.checkpoint(fn, policy=policy)
