"""Kernel dispatch: Pallas on TPU, jnp fallback elsewhere.

The reference gates its CUDA extensions behind lazy imports with Python
fallbacks (apex/multi_tensor_apply/__init__.py:1-4, README.md:90-95); here
the gate is the JAX backend plus an env-var kill switch, and the fallback
is the pure-jnp path which is bitwise-comparable in tests.

Env vars:
  APEX_TPU_DISABLE_PALLAS=1   force the jnp path everywhere
  APEX_TPU_FORCE_PALLAS=1     dispatch off-TPU what the chip dispatches
                              (interpret mode; slow, used by kernel
                              parity tests)
"""

from __future__ import annotations

import os
from typing import Any

import jax

_KERNELS_AVAILABLE = None


def kernels_available() -> bool:
    """True iff the Pallas kernel modules import cleanly (the analogue of
    the reference's `import amp_C` probe, multi_tensor_apply/__init__.py:1-4).
    Off-TPU a failed import selects the jnp path; on TPU it raises — a
    whole kernel family silently running jnp there is a defect, not a
    degradation."""
    global _KERNELS_AVAILABLE
    if _KERNELS_AVAILABLE is None:
        try:
            from . import pallas_multi_tensor  # noqa: F401
            from . import pallas_adam  # noqa: F401
            from . import pallas_layer_norm  # noqa: F401
            from . import pallas_lamb  # noqa: F401
            from . import pallas_flash_attention  # noqa: F401
            _KERNELS_AVAILABLE = True
        except ImportError:
            if backend() == "tpu":
                raise
            _KERNELS_AVAILABLE = False
    return _KERNELS_AVAILABLE


def backend() -> str:
    return jax.default_backend()


def pallas_enabled() -> bool:
    """True on a TPU, and off-TPU under APEX_TPU_FORCE_PALLAS=1, which
    reproduces the chip's gating: the kernels dispatched on hardware
    (fused Adam/LAMB, multi-tensor, flash attention) run Pallas in
    interpret mode while what XLA fuses better (the BatchNorm apply) is
    jnp everywhere.  The L1 cross-product driver trains under it so its
    bitwise comparison matches what hardware executes."""
    if os.environ.get("APEX_TPU_DISABLE_PALLAS") == "1":
        return False
    if not kernels_available():
        return False
    if os.environ.get("APEX_TPU_FORCE_PALLAS") == "1":
        return True
    return backend() == "tpu"


def interpret_mode() -> bool:
    """Pallas interpret=True is needed off-TPU (CPU tests)."""
    return backend() != "tpu"


def use_pallas_for(tree: Any) -> bool:
    if not pallas_enabled():
        return False
    leaves = jax.tree_util.tree_leaves(tree)
    return bool(leaves)
