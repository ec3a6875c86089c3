"""Model/optimizer ingestion for amp.initialize.

The reference casts the model in place and patches its forward to cast
inputs/outputs (apex/amp/_initialize.py:150-268).  Functionally, the model
wrapper owns that behavior: ``AmpModel.init`` produces params already in
the opt-level's dtype (keeping batchnorm fp32 per keep_batchnorm_fp32, like
convert_network, apex/fp16_utils/fp16util.py:60-70), and ``AmpModel.apply``
casts inputs on entry / outputs on exit and installs the O1 cast policy for
the duration of the trace.
"""

from __future__ import annotations

from typing import Any, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from . import policy as _policy
from ._amp_state import maybe_print
from ._process_optimizer import AmpOptimizer
from .frontend import Properties
from .scaler import LossScaler

# NOTE: apex_tpu.nn is imported lazily inside functions — nn.functional
# consults amp.policy at import time, so a module-level import here would
# be circular.

__all__ = ["AmpModel", "AmpOptimizer", "_initialize", "cast_param_tree"]


def cast_param_tree(module, params: dict, dtype,
                    keep_batchnorm_fp32: Optional[bool]) -> dict:
    """Cast a params tree to ``dtype``, skipping fp32-pinned modules
    (BatchNorm/LayerNorm: ``fp32_params``) and a module's own fp32-pinned
    leaves (an expert layer's router: ``fp32_param_names``) when
    keep_batchnorm_fp32 is truthy."""
    keep = bool(keep_batchnorm_fp32)

    def walk(mod, p: Any) -> Any:
        if not isinstance(p, dict):
            if keep and getattr(mod, "fp32_params", False):
                return p
            if jnp.issubdtype(jnp.result_type(p), jnp.floating):
                return p.astype(dtype)
            return p
        out = {}
        for k, v in p.items():
            child = mod._children.get(k)
            if child is not None:
                out[k] = walk(child, v)
            elif keep and k in getattr(mod, "fp32_param_names", ()):
                out[k] = v
            else:
                out[k] = walk(mod, v)
        return out

    return walk(module, params)


def _cast_floats(tree: Any, dtype) -> Any:
    return jax.tree_util.tree_map(
        lambda x: x.astype(dtype)
        if isinstance(x, (jax.Array, jnp.ndarray)) and jnp.issubdtype(
            jnp.result_type(x), jnp.floating) else x, tree)


class AmpModel:
    """Policy-applying functional wrapper around an apex_tpu.nn.Module."""

    def __init__(self, module, properties: Properties,
                 disabled: bool = False):
        self.module = module
        self.properties = properties
        self.disabled = disabled

    # -- params ------------------------------------------------------------
    def init(self, key: jax.Array) -> Tuple[dict, dict]:
        params, state = self.module.init(key)
        return self.cast_params(params), state

    def cast_params(self, params: dict) -> dict:
        props = self.properties
        ct = props.options.get("cast_model_type")
        if self.disabled or ct is None:
            return params
        if jnp.dtype(ct) == jnp.dtype(jnp.float32):
            return _cast_floats(params, jnp.float32)
        return cast_param_tree(self.module, params, ct,
                               props.keep_batchnorm_fp32)

    # -- forward -----------------------------------------------------------
    def _make_policy(self) -> _policy.Policy:
        if self.disabled or not self.properties.patch_torch_functions:
            return _policy.NoPolicy()
        return _policy.CastPolicy(self.properties.half_jnp_dtype)

    def apply(self, params: dict, *args, state: Optional[dict] = None,
              train: bool = False, rng: Optional[jax.Array] = None,
              mutable: bool = True, **kwargs):
        props = self.properties
        ct = None if self.disabled else props.options.get("cast_model_type")
        # the casts at the model's boundary belong to the model's phase
        # (nn.apply opens the same root scope around the module itself)
        if ct is not None and jnp.dtype(ct) != jnp.dtype(jnp.float32):
            with jax.named_scope("model"):
                args = _cast_floats(args, ct)
                kwargs = _cast_floats(kwargs, ct)
        from ..nn import module as _nn_module
        with _policy.use_policy(self._make_policy()):
            out, new_state = _nn_module.apply(
                self.module, params, *args, state=state, train=train,
                rng=rng, mutable=mutable, **kwargs)
        co = None if self.disabled else props.options.get("cast_model_outputs")
        with jax.named_scope("model"):
            if co is not None:
                out = _cast_floats(out, co)
            elif ct is not None and jnp.dtype(ct) != jnp.dtype(jnp.float32):
                # O2/O3 cast model outputs back to fp32 (reference
                # _initialize.py:197-208) so losses run in fp32.
                out = _cast_floats(out, jnp.float32)
        return out, new_state

    __call__ = apply

    def __getattr__(self, name):
        return getattr(self.module, name)


def _wrap_optimizer(opt, props: Properties,
                    disabled: bool) -> AmpOptimizer:
    if isinstance(opt, AmpOptimizer):
        raise RuntimeError("amp.initialize should be called only once; "
                           "received an already-wrapped optimizer.")
    if disabled:
        scaler = LossScaler(1.0)
        return AmpOptimizer(opt, scaler, master_weights=False,
                            num_losses=props.num_losses)
    scaler = LossScaler(
        props.loss_scale if props.loss_scale is not None else "dynamic",
        min_loss_scale=props.min_loss_scale,
        max_loss_scale=props.max_loss_scale)
    master = bool(props.master_weights)
    return AmpOptimizer(opt, scaler, master_weights=master,
                        num_losses=props.num_losses)


def _initialize(model, optimizers, properties: Properties,
                disabled: bool = False):
    from ..nn.module import Module as _Module
    single_model = not isinstance(model, (list, tuple))
    models = [model] if single_model else list(model)
    for m in models:
        if isinstance(m, AmpModel):
            raise RuntimeError("amp.initialize should be called only once; "
                               "received an already-wrapped model.")
        if not isinstance(m, _Module):
            raise TypeError(
                f"amp.initialize expected an apex_tpu.nn.Module, got "
                f"{type(m).__name__}")

    wrapped_models = [AmpModel(m, properties, disabled) for m in models]

    if properties.patch_torch_functions and not disabled:
        # install the process-wide O1 policy, the analogue of amp.init()'s
        # monkey-patching (apex/amp/amp.py:68-177)
        _policy.set_policy(_policy.CastPolicy(properties.half_jnp_dtype))

    if optimizers is None:
        out_opt: Any = None
    else:
        single_opt = not isinstance(optimizers, (list, tuple))
        opts = [optimizers] if single_opt else list(optimizers)
        wrapped = [_wrap_optimizer(o, properties, disabled) for o in opts]
        out_opt = wrapped[0] if single_opt else wrapped

    out_model = wrapped_models[0] if single_model else wrapped_models
    if out_opt is None:
        return out_model
    return out_model, out_opt
