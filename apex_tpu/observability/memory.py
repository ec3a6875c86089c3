"""Memory observability: compiled memory plans, analytic liveness, and
live on-device gauges.

Three complementary views of "how much HBM does this cost", each with a
different trust level:

1. **Compiled plan** (:func:`memory_plan`): XLA's own
   ``Compiled.memory_analysis()`` — argument / output / temp /
   generated-code bytes and the donation-alias credit, i.e. what the
   executable will actually reserve; ``kind: memory`` records carry
   it as ``peak_bytes``.
2. **Analytic liveness** (:func:`jaxpr_live_bytes`): a static
   last-use scan over the traced jaxpr — cheap enough for the lint
   path (no compile), good enough to catch a graph suddenly keeping a
   second cache copy or doubling its fp32 temp bytes under O2
   (``analysis.rules.MemoryBudgetRule``).
3. **Live gauges** (:func:`live_array_bytes` /
   :func:`record_live_arrays`): ``jax.live_arrays()`` census wired
   into a :class:`MetricsRegistry` — what is resident *right now*
   (``Engine.stats()`` reports its KV-cache share of it).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import numpy as np

from .exporters import MEMORY_PLAN_KEYS as MEMORY_PLAN_FIELDS

__all__ = ["memory_plan", "jaxpr_live_bytes", "live_array_bytes",
           "record_live_arrays", "device_memory_stats",
           "MEMORY_PLAN_FIELDS"]


def memory_plan(compiled) -> Dict[str, int]:
    """Normalize ``Compiled.memory_analysis()`` into a plain dict.

    ``peak_bytes`` is the executable's device-memory high-water mark:
    arguments + outputs + temps + generated code, minus the
    donation-alias credit (a donated buffer's output shares its
    argument's storage, so it is not charged twice)."""
    ma = compiled.memory_analysis()
    # built from the validator's own key tuple, so producer and schema
    # cannot drift ("argument_bytes" <-> ma.argument_size_in_bytes)
    plan = {key: int(getattr(ma, key.replace("_bytes",
                                             "_size_in_bytes")))
            for key in MEMORY_PLAN_FIELDS}
    plan["peak_bytes"] = (plan["argument_bytes"] + plan["output_bytes"]
                          + plan["temp_bytes"]
                          + plan["generated_code_bytes"]
                          - plan["alias_bytes"])
    return plan


# -- analytic liveness over a jaxpr ----------------------------------------

def _aval_bytes(v) -> int:
    from .costmodel import _nbytes
    return _nbytes(v)


def _unwrap(jaxpr):
    """Descend through single-eqn wrapper layers (shard_map / pjit /
    remat / custom-vjp): the per-device body is where liveness lives —
    treating the wrapper eqn atomically would make every budget
    vacuously equal to args+outputs."""
    import jax.extend.core
    from .costmodel import _subjaxprs
    if isinstance(jaxpr, jax.extend.core.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    while len(jaxpr.eqns) == 1:
        subs = _subjaxprs(jaxpr.eqns[0])
        if len(subs) != 1:
            break
        jaxpr = subs[0]
        if isinstance(jaxpr, jax.extend.core.ClosedJaxpr):
            jaxpr = jaxpr.jaxpr
    return jaxpr


def jaxpr_live_bytes(jaxpr) -> Dict[str, Any]:
    """Static peak-live-bytes estimate via a last-use scan.

    Walks the (unwrapped) top-level eqns in program order: an eqn's
    outputs go live when it runs, operands die after their last use.
    Sub-jaxpr-carrying eqns (scan bodies etc.) are treated atomically —
    their internal temps are not modeled, so this is a *lower*-bound
    estimate; the compiled plan is the ground truth.  Returns::

        {"peak_live_bytes": ...,        # args + consts + peak temps
         "argument_bytes": ...,
         "peak_temp_bytes": ...,        # intermediates only
         "peak_temp_bytes_by_dtype": {"float32": ..., ...}}

    The per-dtype temp peaks are what ``MemoryBudgetRule`` budgets: an
    fp32 upcast sneaking into an O2 graph shows up as the float32 temp
    peak doubling while the bf16 peak is unchanged.
    """
    import jax.extend.core
    jx = _unwrap(jaxpr)
    const_bytes = sum(_aval_bytes(v) for v in jx.constvars)
    arg_bytes = sum(_aval_bytes(v) for v in jx.invars)

    last_use: Dict[Any, int] = {}
    n = len(jx.eqns)
    for i, eqn in enumerate(jx.eqns):
        for v in eqn.invars:
            if isinstance(v, jax.extend.core.Var):
                last_use[v] = i
    for v in jx.outvars:
        if isinstance(v, jax.extend.core.Var):
            last_use[v] = n            # outputs live to the end

    live = 0
    live_by_dtype: Dict[str, int] = {}
    peak = 0
    peak_by_dtype: Dict[str, int] = {}
    args = set(v for v in list(jx.invars) + list(jx.constvars))
    for i, eqn in enumerate(jx.eqns):
        for v in eqn.outvars:
            b = _aval_bytes(v)
            if not b or v not in last_use:
                continue               # dead value: XLA DCEs it
            live += b
            dt = str(v.aval.dtype)
            live_by_dtype[dt] = live_by_dtype.get(dt, 0) + b
        peak = max(peak, live)
        for dt, b in live_by_dtype.items():
            if b > peak_by_dtype.get(dt, 0):
                peak_by_dtype[dt] = b
        seen_ids = set()
        for v in list(eqn.invars) + list(eqn.outvars):
            if not isinstance(v, jax.extend.core.Var) or v in args \
                    or id(v) in seen_ids:
                continue
            seen_ids.add(id(v))
            if last_use.get(v) == i:
                b = _aval_bytes(v)
                live -= b
                dt = str(v.aval.dtype)
                live_by_dtype[dt] = live_by_dtype.get(dt, 0) - b
    return {
        "peak_live_bytes": int(arg_bytes + const_bytes + peak),
        "argument_bytes": int(arg_bytes + const_bytes),
        "peak_temp_bytes": int(peak),
        "peak_temp_bytes_by_dtype": {k: int(v)
                                     for k, v in peak_by_dtype.items()},
    }


# -- live on-device census -------------------------------------------------

def live_array_bytes(platform: Optional[str] = None) -> Dict[str, Any]:
    """Census of ``jax.live_arrays()``: total resident bytes and buffer
    count (optionally restricted to one platform).  Committed sharded
    arrays count each shard once via their addressable shards."""
    import jax
    total = 0
    count = 0
    by_platform: Dict[str, int] = {}
    for a in jax.live_arrays():
        try:
            nbytes = int(a.nbytes)
            plat = a.devices().pop().platform if a.devices() else "?"
        except Exception:
            continue
        if platform is not None and plat != platform:
            continue
        total += nbytes
        count += 1
        by_platform[plat] = by_platform.get(plat, 0) + nbytes
    return {"bytes": total, "arrays": count, "by_platform": by_platform}


def device_memory_stats(device=None) -> Optional[Dict[str, int]]:
    """``device.memory_stats()`` where the backend supports it (TPU:
    ``bytes_in_use`` / ``bytes_limit``); None on CPU-style backends —
    callers fall back to the live-array census."""
    import jax
    d = device if device is not None else jax.devices()[0]
    try:
        stats = d.memory_stats()
    except Exception:
        return None
    if not stats:
        return None
    out = {}
    for key in ("bytes_in_use", "bytes_limit", "peak_bytes_in_use"):
        if key in stats:
            out[key] = int(stats[key])
    return out or None


def record_live_arrays(registry=None, platform: Optional[str] = None
                       ) -> Dict[str, Any]:
    """Fold the live-array census (and hardware memory stats when the
    backend exposes them) into gauges on ``registry`` (default process
    registry): ``device_live_bytes``, ``device_live_arrays``, and — on
    backends with real memory stats — ``device_bytes_in_use`` /
    ``device_bytes_limit``.  Returns the census dict."""
    from .metrics import get_registry
    reg = registry if registry is not None else get_registry()
    census = live_array_bytes(platform=platform)
    reg.gauge("device_live_bytes",
              help="bytes of live jax arrays (host census)"
              ).set(census["bytes"])
    reg.gauge("device_live_arrays",
              help="count of live jax arrays").set(census["arrays"])
    hw = device_memory_stats()
    if hw:
        if "bytes_in_use" in hw:
            reg.gauge("device_bytes_in_use",
                      help="backend-reported bytes in use"
                      ).set(hw["bytes_in_use"])
        if "bytes_limit" in hw:
            reg.gauge("device_bytes_limit",
                      help="backend-reported memory capacity"
                      ).set(hw["bytes_limit"])
        census["memory_stats"] = hw
    return census
