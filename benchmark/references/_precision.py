"""How a plain reference multiplies matrices.  ``float32`` is the reference
proper (full precision: on a TPU a float32 matmul runs in bf16 passes unless
told otherwise).  The lower entries exist for the controls: the reference put
in the program's place, computed one precision below what a configuration
states, has to come out as not correct."""

import jax
import jax.numpy as jnp

# the nearest precision below each one a configuration may state
NEXT_LOWER = {"float32": "bfloat16", "bfloat16": "fp8", "float16": "fp8", "int8": "int4"}


def _round(x, precision):
    """Round the operands of a matmul as the lower precision would hold them.
    ``lax.reduce_precision`` is explicit: XLA may drop a convert to bf16 and
    back as excess precision, which would make the control the reference."""
    if precision == "bfloat16":
        low = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
        return x + jax.lax.stop_gradient(low - x)
    if precision == "fp8":
        # 4 exponent and 3 mantissa bits with a scale per row of the contraction
        # (dynamic scaling, as fp8 matmuls are run): the row's largest value
        # lands on 240, the largest finite value of that format with infinities
        scale = jax.lax.stop_gradient(jnp.max(jnp.abs(x), axis=-1, keepdims=True)) / 240.0
        scale = jnp.where(scale > 0, scale, 1.0)
        low = jax.lax.reduce_precision(x / scale, exponent_bits=4, mantissa_bits=3) * scale
        # straight through: reduce_precision's own transpose would round the
        # cotangent to 4 exponent bits too, and flush most of it to zero
        return x + jax.lax.stop_gradient(low - x)
    raise ValueError(f"unknown precision {precision!r}")


def store(x, dtype_name):
    """A value as a parameter store of ``dtype_name`` would hold it."""
    if dtype_name == "float32":
        return x
    if dtype_name == "bfloat16":
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    raise ValueError(f"unknown parameter dtype {dtype_name!r}")


def matmul(x, w, precision="float32"):
    """x @ w.T for a torch-style (out, in) weight, accumulated in float32."""
    if precision == "float32":
        return jnp.einsum("...i,oi->...o", x, w, precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)
    return jnp.einsum("...i,oi->...o", _round(x, precision), _round(w, precision),
                      precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32)


def einsum(spec, a, b, precision="float32"):
    if precision == "float32":
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)
    # attention's own products stay in bf16 under fp8, as fp8 recipes run them
    attn = "bfloat16" if precision == "fp8" else precision
    return jnp.einsum(spec, _round(a, attn), _round(b, attn),
                      precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32)


def to_f32(tree):
    return jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32) if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)
