"""Functional module system for apex_tpu.

The reference toolkit (NVIDIA Apex) layers itself on torch.nn's stateful
modules and monkey-patches their internals (apex/amp/_initialize.py:197-208,
apex/amp/amp.py:68-177).  On TPU/JAX the idiomatic shape is functional: a
module is a *description* (hyperparameters + submodule tree) and parameters
live in an external pytree.  ``Module`` here provides:

- automatic submodule registration via attribute assignment (like torch.nn),
- ``init(key)`` producing a nested params dict mirroring the attribute tree,
- mutable-state handling (BatchNorm running stats) through a flat,
  path-keyed state dict threaded by :func:`apply` — so user ``forward``
  code only passes params, exactly like torch code only passes tensors,
- train/eval and RNG plumbing through an apply-context, so dropout and
  batchnorm behave like ``model.train()`` / ``model.eval()`` without the
  user threading flags through every call.

Everything is jit-safe: the context only ever holds tracers that came in
through :func:`apply`'s arguments, and state updates are returned
functionally.
"""

from __future__ import annotations

import threading
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "Module",
    "ModuleList",
    "Sequential",
    "apply",
    "init",
    "current_context",
    "ApplyContext",
]


class _ContextStack(threading.local):
    def __init__(self):
        self.stack: List["ApplyContext"] = []


_CTX = _ContextStack()


class ApplyContext:
    """Per-apply bookkeeping: mutable state in/out, train flag, RNGs."""

    def __init__(self, state: Optional[Dict[str, Any]], train: bool,
                 rng: Optional[jax.Array], mutable: bool):
        self.state_in: Dict[str, Any] = dict(state or {})
        self.state_out: Dict[str, Any] = {}
        self.train = bool(train)
        self.mutable = bool(mutable)
        self._rng = rng
        self._rng_count = 0

    # -- state ------------------------------------------------------------
    def get_state(self, path: str) -> Any:
        if path in self.state_out:
            return self.state_out[path]
        return self.state_in.get(path)

    def set_state(self, path: str, value: Any) -> None:
        if self.mutable:
            self.state_out[path] = value

    # -- rng --------------------------------------------------------------
    def make_rng(self) -> jax.Array:
        if self._rng is None:
            raise ValueError(
                "This apply() needs an rng= argument (a module used dropout "
                "or another stochastic op in train mode).")
        self._rng_count += 1
        return jax.random.fold_in(self._rng, self._rng_count)

    def merged_state(self) -> Dict[str, Any]:
        out = dict(self.state_in)
        out.update(self.state_out)
        return out


def current_context() -> Optional[ApplyContext]:
    return _CTX.stack[-1] if _CTX.stack else None


class Module:
    """Base class: a hyperparameter container with a named submodule tree."""

    def __init__(self):
        object.__setattr__(self, "_children", {})
        object.__setattr__(self, "_parent", None)
        object.__setattr__(self, "_name", None)

    # -- tree plumbing ----------------------------------------------------
    def __setattr__(self, name: str, value: Any) -> None:
        if isinstance(value, Module):
            self._children[name] = value
            object.__setattr__(value, "_parent", self)
            object.__setattr__(value, "_name", name)
        elif name in self._children and not isinstance(value, Module):
            del self._children[name]
        object.__setattr__(self, name, value)

    def _replace_child(self, name: str, new: "Module") -> None:
        """Swap a registered child (used by convert_syncbn_model-style passes)."""
        setattr(self, name, new)

    @property
    def path(self) -> str:
        parts: List[str] = []
        node: Optional[Module] = self
        while node is not None and node._name is not None:
            parts.append(node._name)
            node = node._parent
        return ".".join(reversed(parts))

    def named_children(self) -> Iterator[Tuple[str, "Module"]]:
        return iter(list(self._children.items()))

    def modules(self) -> Iterator["Module"]:
        yield self
        for _, c in self.named_children():
            yield from c.modules()

    def named_modules(self, prefix: str = "") -> Iterator[Tuple[str, "Module"]]:
        yield prefix, self
        for name, c in self.named_children():
            sub = f"{prefix}.{name}" if prefix else name
            yield from c.named_modules(sub)

    # -- parameter / state creation --------------------------------------
    def create_params(self, key: jax.Array) -> Dict[str, Any]:
        """Leaf hook: return this module's own parameter dict (no children)."""
        return {}

    def create_state(self) -> Optional[Dict[str, Any]]:
        """Leaf hook: return this module's own mutable state dict, if any."""
        return None

    def init(self, key: jax.Array) -> Tuple[Dict[str, Any], Dict[str, Any]]:
        """Build (params, state) for this module and all descendants.

        ``params`` is nested mirroring attribute names; ``state`` is flat,
        keyed by dotted module path (jit-friendly and immune to the param
        tree being sliced by optimizers).
        """
        params: Dict[str, Any] = {}
        state: Dict[str, Any] = {}
        own_key, child_key = jax.random.split(key) if self._children else (key, None)
        own = self.create_params(own_key)
        if own:
            params.update(own)
        own_state = self.create_state()
        if own_state is not None:
            state[self.path] = own_state
        if self._children:
            keys = jax.random.split(child_key, len(self._children))
            for (name, child), k in zip(self._children.items(), keys):
                p, s = child.init(k)
                if p:
                    params[name] = p
                state.update(s)
        return params, state

    # -- forward ----------------------------------------------------------
    def forward(self, params: Dict[str, Any], *args, **kwargs):
        raise NotImplementedError(type(self).__name__)

    def __call__(self, params: Dict[str, Any], *args, **kwargs):
        # the module path names the HLO (bert/encoder/3/attention/...),
        # under the root scope ``model`` that :func:`apply` opens
        with jax.named_scope(self._name or type(self).__name__):
            return self.forward(params, *args, **kwargs)

    def apply(self, params: Dict[str, Any], *args,
              state: Optional[Dict[str, Any]] = None, train: bool = False,
              rng: Optional[jax.Array] = None, mutable: bool = True,
              **kwargs):
        """Functional apply returning ``(out, new_state)`` — see the
        module-level :func:`apply`."""
        return apply(self, params, *args, state=state, train=train,
                     rng=rng, mutable=mutable, **kwargs)

    # -- conveniences -----------------------------------------------------
    def sub(self, params: Dict[str, Any], name: str) -> Dict[str, Any]:
        return params.get(name, {})

    def __repr__(self) -> str:
        lines = [type(self).__name__ + "("]
        for name, c in self.named_children():
            body = repr(c).splitlines()
            lines.append(f"  ({name}): " + body[0])
            lines.extend("  " + b for b in body[1:])
        lines.append(")")
        return "\n".join(lines) if len(lines) > 2 else type(self).__name__ + "()"


class ModuleList(Module):
    """An indexable list of submodules, registered as children '0','1',..."""

    def __init__(self, mods: Optional[List[Module]] = None):
        super().__init__()
        self._len = 0
        for m in (mods or []):
            self.append(m)

    def append(self, mod: Module) -> "ModuleList":
        setattr(self, str(self._len), mod)
        self._len += 1
        return self

    def __len__(self) -> int:
        return self._len

    def __getitem__(self, idx: int) -> Module:
        if isinstance(idx, slice):
            return [getattr(self, str(i)) for i in range(*idx.indices(self._len))]
        if idx < 0:
            idx += self._len
        return getattr(self, str(idx))

    def __iter__(self) -> Iterator[Module]:
        return (self[i] for i in range(self._len))

    def __setitem__(self, idx: int, mod: Module) -> None:
        if idx < 0:
            idx += self._len
        setattr(self, str(idx), mod)


class Sequential(ModuleList):
    """Chains children; each child is called as child(params[name], x)."""

    def forward(self, params, x):
        for i, mod in enumerate(self):
            x = mod(params.get(str(i), {}), x)
        return x


def init(module: Module, key: jax.Array) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    return module.init(key)


def apply(module: Module, params: Dict[str, Any], *args,
          state: Optional[Dict[str, Any]] = None, train: bool = False,
          rng: Optional[jax.Array] = None, mutable: bool = True, **kwargs):
    """Run ``module`` functionally.

    Returns ``(out, new_state)``. ``new_state`` equals ``state`` with any
    updates applied (BatchNorm running stats in train mode, etc.).  With
    ``mutable=False`` state writes are dropped and ``new_state is state``-
    equivalent, which keeps eval paths trivially pure.
    """
    ctx = ApplyContext(state, train, rng, mutable)
    _CTX.stack.append(ctx)
    try:
        with jax.named_scope("model"):
            out = module(params, *args, **kwargs)
    finally:
        _CTX.stack.pop()
    return out, ctx.merged_state()
