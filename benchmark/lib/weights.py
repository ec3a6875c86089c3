"""Weights from the seed: one jitted call on the device, in the type they are
served or trained in.  The benchmark makes them; the program and the plain
reference are both handed the same tree."""

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A key from any whole number (seeds run past 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def make_weights(shapes, seed: int, std: float = 0.02, sharding=None):
    """``shapes``: a tree of ShapeDtypeStruct in the program's layout.  One
    draw of N(0, std) for all float elements, cut into the leaves in tree
    order and rounded to each leaf's dtype; a 1-D ``weight`` (a LayerNorm
    gain) is 1 + its draw.  One draw and static slices keep the program small
    to compile."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    sizes = [int(np.prod(leaf.shape)) if jnp.issubdtype(leaf.dtype, jnp.floating) else 0
             for _, leaf in flat]
    starts = np.concatenate([[0], np.cumsum(sizes)])

    def build(key):
        noise = std * jax.random.normal(key, (int(starts[-1]),), jnp.float32)
        leaves = []
        for i, (path, leaf) in enumerate(flat):
            if not sizes[i]:
                leaves.append(jnp.zeros(leaf.shape, leaf.dtype))
                continue
            x = noise[int(starts[i]):int(starts[i + 1])].reshape(leaf.shape)
            if len(leaf.shape) == 1 and jax.tree_util.keystr(path).endswith("['weight']"):
                x = 1.0 + x
            leaves.append(x.astype(leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build, out_shardings=sharding)(seed_key(seed))
