"""Sharded/async checkpointing via Orbax — the TPU-native alternative to
the npz path in :mod:`apex_tpu.utils.checkpoint`.

The npz checkpointer (utils/checkpoint.py) gathers every leaf to host —
correct and dependency-free, but on a pod that funnels the whole model
through one host and blocks the step loop.  Orbax writes each shard from
the process that owns it (TensorStore/OCDBT) and can do so
asynchronously, which is how large sharded TP/PP state is checkpointed
in practice.  This module is a thin adapter keeping the same call shape
as the npz API:

    from apex_tpu.utils import checkpoint_orbax as ckpt
    ckpt.save_checkpoint(dir, step, {"params": params, "opt": opt_state})
    state = ckpt.restore_checkpoint(dir, template)          # latest
    state = ckpt.restore_checkpoint(dir, template, step=7)

``template`` supplies structure/shape/dtype AND SHARDING: pass the live
state (or equivalently shaped abstract arrays with shardings) so every
restored leaf lands already-sharded on its devices — no host round trip.
Restore-time reshard is supported: a template with a different mesh
layout restores into that layout.

Falls back cleanly when orbax is unavailable (import guarded); callers
needing the guaranteed-present path use the npz module.

Telemetry (shared with the npz path via
:func:`~apex_tpu.utils.checkpoint.record_checkpoint_io`): every save /
restore lands in the process registry's
``checkpoint_save_seconds`` / ``checkpoint_restore_seconds``
histograms and the ``checkpoint_snapshot_bytes`` gauge, and every
**durable** save appends a ``checkpoint_saved`` flight-ring event —
for a sync save at return, for an async save at the join (``wait()``
or the next save), because only then has the write actually succeeded
and only then may the training-run supervisor's progress watermark
consume it.
"""

from __future__ import annotations

import json
import os
import re
import time
import zlib
from typing import Any, Optional

import jax
import numpy as np

from .checkpoint import (CheckpointCorrupt, _flat_layouts, flat_orders,
                         record_checkpoint_io, tree_bytes, tree_checksum)

__all__ = ["CheckpointCorrupt", "save_checkpoint", "restore_checkpoint",
           "latest_step", "available_steps", "load_data_state"]

_STEP_RE = re.compile(r"^step_(\d+)$")

# content-checksum sidecar inside each step dir (Orbax owns the tree
# layout, so the checksum rides alongside rather than inside): written
# only once the save is DURABLE (sync: at return; async: at the join).
# A torn background write leaves no sidecar — but so does a genuinely
# old (pre-checksum) snapshot, so every save ALSO drops a pending
# marker NEXT TO the step dir (Orbax's force=True clears the target
# dir itself) before the write starts and removes it at the join:
# marker-without-sidecar = a save that never joined = corruption;
# neither file = legacy = trusted like before.
_CHECKSUM_FILE = "_apex_checksum.json"
_PENDING_FMT = "_apex_pending_step_{step}.json"


def _keyed_leaves(tree: Any) -> dict:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(path): leaf for path, leaf in flat}


def _chain_data_state(crc: int, data_state: Optional[dict]) -> int:
    """Fold the data-state blob into the content crc (the npz path
    gets this for free by storing the blob as a checksummed leaf):
    a tampered or torn cursor fails verification like any leaf."""
    if data_state is None:
        return crc
    blob = json.dumps(data_state, sort_keys=True).encode()
    return zlib.crc32(blob, crc) & 0xFFFFFFFF


def _write_checksum(path: str, crc: int, nbytes: int, dtypes: dict,
                    data_state: Optional[dict] = None,
                    flat_order: Optional[dict] = None) -> None:
    side = os.path.join(path, _CHECKSUM_FILE)
    tmp = side + ".tmp"
    with open(tmp, "w") as f:
        # the per-leaf dtypes the crc was computed over: a restore
        # into a template with DIFFERENT dtypes casts the leaves
        # (supported by contract), and a checksum over the cast bytes
        # cannot match — the verifier uses this map to know when
        # content verification is possible at all.  data_state (the
        # optional pipeline cursor) rides in the sidecar and is
        # chained into the crc, so it shares the durability story:
        # written only at the join, verified on read.
        meta = {"crc32": int(crc), "tree_bytes": int(nbytes),
                "dtypes": dtypes}
        if flat_order:
            # the order amp's flat buffers hold their leaves in
            # (checkpoint.flat_orders); absent = tree order
            meta["flat_order"] = flat_order
        if data_state is not None:
            meta["data_state"] = data_state
            # a crc over the blob ALONE, so load_data_state can verify
            # the cursor without restoring (and re-checksumming) the
            # whole tree the chained crc32 above binds it to
            meta["data_state_crc32"] = _chain_data_state(0, data_state)
        json.dump(meta, f)
    os.replace(tmp, side)


def _mgr_dir(ckpt_dir: str) -> str:
    return os.path.abspath(ckpt_dir)


def _prune(ckpt_dir: str, keep: int) -> None:
    import shutil
    for s in available_steps(ckpt_dir)[:-keep]:
        shutil.rmtree(os.path.join(_mgr_dir(ckpt_dir), f"step_{s}"),
                      ignore_errors=True)


def save_checkpoint(ckpt_dir: str, step: int, tree: Any,
                    keep: Optional[int] = None,
                    async_save: bool = False,
                    data_state: Optional[dict] = None) -> str:
    """Write ``tree`` under ``ckpt_dir/step_N`` (sharded, per-process).

    ``async_save=True`` returns while the write completes in the
    background (call :func:`wait` or save again to join — a new save
    first joins any pending one, so write errors always surface).
    ``keep`` prunes to the most recent N steps after the save has
    actually SUCCEEDED (for an async save, at join time)."""
    import orbax.checkpoint as ocp
    if keep is not None and keep < 1:
        raise ValueError(f"keep must be >= 1, got {keep}")
    wait()                        # join + surface any pending async save
    path = os.path.join(_mgr_dir(ckpt_dir), f"step_{int(step)}")
    t0 = time.perf_counter()
    nbytes = tree_bytes(tree)
    # content checksum of the tree being written (host gather — the
    # price of verifiable snapshots; restore recomputes it from what
    # it read back).  Computed BEFORE the background write starts so
    # it describes exactly the intended content.
    leaves = _keyed_leaves(tree)
    crc = _chain_data_state(tree_checksum(leaves), data_state)
    dtypes = {k: str(np.asarray(v).dtype) for k, v in leaves.items()}
    flat_order = flat_orders(tree)
    # pending marker BEFORE the write starts: a process dying mid-save
    # leaves marker-without-sidecar, which restore distinguishes from
    # a legacy (pre-checksum) snapshot and flags as corrupt
    os.makedirs(_mgr_dir(ckpt_dir), exist_ok=True)
    pending = os.path.join(_mgr_dir(ckpt_dir),
                           _PENDING_FMT.format(step=int(step)))
    with open(pending, "w") as f:
        json.dump({"step": int(step)}, f)
    ckptr = (ocp.AsyncCheckpointer(ocp.StandardCheckpointHandler())
             if async_save
             else ocp.Checkpointer(ocp.StandardCheckpointHandler()))
    ckptr.save(path, tree, force=True)
    if not async_save:
        ckptr.close()
        _write_checksum(path, crc, nbytes, dtypes, data_state, flat_order)
        os.unlink(pending)
        record_checkpoint_io("save", time.perf_counter() - t0,
                             step=int(step), nbytes=nbytes, path=path)
        if keep is not None:
            _prune(ckpt_dir, keep)
    else:
        global _pending
        # pruning AND the checkpoint_saved telemetry are deferred to
        # the join: a failed background write can't have already
        # deleted the older good checkpoints, and must not have
        # emitted a progress event for a snapshot that never landed.
        # The checksum sidecar is deferred the same way: only a
        # JOINED (durable) save gets one, so a torn background write
        # is visibly unverified.
        _pending = (ckptr, ckpt_dir, keep, int(step), path, nbytes,
                    crc, dtypes, data_state, flat_order, t0)
    return path


_pending = None


def wait() -> None:
    """Join an in-flight async save (then apply its deferred pruning
    and emit its deferred ``checkpoint_saved`` telemetry — the save is
    only durable now)."""
    global _pending
    if _pending is not None:
        (ckptr, ckpt_dir, keep, step, path, nbytes, crc, dtypes,
         data_state, flat_order, t0) = _pending
        _pending = None
        ckptr.wait_until_finished()
        ckptr.close()
        _write_checksum(path, crc, nbytes, dtypes, data_state, flat_order)
        try:
            os.unlink(os.path.join(
                _mgr_dir(ckpt_dir), _PENDING_FMT.format(step=step)))
        except OSError:
            pass
        record_checkpoint_io("save", time.perf_counter() - t0,
                             step=step, nbytes=nbytes, path=path,
                             async_save=True)
        if keep is not None:
            _prune(ckpt_dir, keep)


def available_steps(ckpt_dir: str) -> list:
    d = _mgr_dir(ckpt_dir)
    if not os.path.isdir(d):
        return []
    out = []
    for name in os.listdir(d):
        m = _STEP_RE.match(name)
        if m:
            out.append(int(m.group(1)))
    return sorted(out)


def latest_step(ckpt_dir: str) -> Optional[int]:
    steps = available_steps(ckpt_dir)
    return steps[-1] if steps else None


def _refuse_other_flat_order(path: str, template: Any) -> None:
    """Orbax restores by shape alone, and amp's flat buffers can have one
    length in two orders (tree order before the layout kept its leaves by
    dtype): a snapshot whose sidecar does not say the template's order
    is refused where the two orders differ (more than one segment);
    ``utils.checkpoint`` (npz) moves such a snapshot leaf by leaf."""
    saved = {}
    side = os.path.join(path, _CHECKSUM_FILE)
    if os.path.exists(side):
        try:
            with open(side) as f:
                saved = json.load(f).get("flat_order", {})
        except (OSError, ValueError):
            pass                  # restore's own read of it reports that
    for n, lay in _flat_layouts(template).items():
        if len(lay.segments) > 1 and saved.get(str(n), "tree") != lay.order:
            raise ValueError(
                f"{path}: its flat optimizer buffers are in "
                f"{saved.get(str(n), 'tree')!r} order, the template's "
                f"layout keeps {lay.order!r} order: restore it with the "
                f"code that wrote it and save it through utils.checkpoint "
                f"(npz), whose restore moves the leaves")


def restore_checkpoint(ckpt_dir: str, template: Any,
                       step: Optional[int] = None) -> Any:
    """Restore into ``template``'s structure, dtypes, AND shardings.

    Leaves come back as jax.Arrays sharded like the template's (live
    arrays or ShapeDtypeStructs with ``.sharding``); a different mesh
    layout in the template reshards on read."""
    import orbax.checkpoint as ocp
    if step is None:
        step = latest_step(ckpt_dir)
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {ckpt_dir}")
    path = os.path.join(_mgr_dir(ckpt_dir), f"step_{int(step)}")
    _refuse_other_flat_order(path, template)

    def to_abstract(leaf):
        if hasattr(leaf, "sharding"):
            return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                        sharding=leaf.sharding)
        return jax.ShapeDtypeStruct(jax.numpy.asarray(leaf).shape,
                                    jax.numpy.asarray(leaf).dtype)

    t0 = time.perf_counter()
    abstract = jax.tree_util.tree_map(to_abstract, template)
    try:
        with ocp.Checkpointer(ocp.StandardCheckpointHandler()) as ckptr:
            restored = ckptr.restore(path, abstract)
    except (FileNotFoundError, ValueError, KeyError) as e:
        # a torn step dir (interrupted write, missing TensorStore
        # files) fails inside Orbax's own readers — surface it as the
        # corruption it is so the recovery controller's fallback loop
        # treats both backends the same way
        raise CheckpointCorrupt(f"{path}: unreadable snapshot ({e})")
    # content verification against the durability sidecar.  A pending
    # marker WITHOUT a sidecar means the save never joined (process
    # died mid-async-write): the step dir may be readable yet stale or
    # partial, and must not restore silently — this is what makes a
    # torn write distinguishable from a genuinely pre-checksum legacy
    # snapshot (neither file), which loads as-is.
    side = os.path.join(path, _CHECKSUM_FILE)
    pending = os.path.join(_mgr_dir(ckpt_dir),
                           _PENDING_FMT.format(step=int(step)))
    if not os.path.exists(side) and os.path.exists(pending):
        raise CheckpointCorrupt(
            f"{path}: save was never joined (pending marker present, "
            f"no durability sidecar) — torn async write")
    if os.path.exists(side):
        try:
            with open(side) as f:
                meta = json.load(f)
            want = meta["crc32"]
        except (OSError, ValueError, KeyError) as e:
            raise CheckpointCorrupt(f"{side}: unreadable checksum "
                                    f"sidecar ({e})")
        leaves = _keyed_leaves(restored)
        # the sidecar crc was computed over the SAVED dtypes; a
        # template with different dtypes casts the restore (supported
        # by contract), and bytes after a cast cannot match — only
        # verify when every leaf came back at its recorded dtype
        saved_dt = meta.get("dtypes")
        comparable = saved_dt is None or all(
            str(np.asarray(v).dtype) == saved_dt.get(k)
            for k, v in leaves.items())
        if comparable:
            got = _chain_data_state(tree_checksum(leaves),
                                    meta.get("data_state"))
            if int(want) != got:
                raise CheckpointCorrupt(
                    f"{path}: content checksum mismatch (sidecar "
                    f"{int(want):#010x}, recomputed {got:#010x})")
    record_checkpoint_io("restore", time.perf_counter() - t0,
                         step=int(step), nbytes=tree_bytes(restored),
                         path=path)
    return restored


def load_data_state(ckpt_dir: str,
                    step: Optional[int] = None) -> Optional[dict]:
    """Read the snapshot's data-pipeline cursor blob from the
    durability sidecar (written only at the join, crc-chained — same
    contract as the npz path's :func:`~.checkpoint.load_data_state`).
    ``None`` when the snapshot carries none."""
    if step is None:
        step = latest_step(ckpt_dir)
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {ckpt_dir}")
    path = os.path.join(_mgr_dir(ckpt_dir), f"step_{int(step)}")
    if not os.path.isdir(path):
        raise FileNotFoundError(path)
    side = os.path.join(path, _CHECKSUM_FILE)
    if not os.path.exists(side):
        return None
    try:
        with open(side) as f:
            meta = json.load(f)
    except (OSError, ValueError) as e:
        raise CheckpointCorrupt(f"{side}: unreadable checksum "
                                f"sidecar ({e})")
    ds = meta.get("data_state")
    if ds is not None:
        want = meta.get("data_state_crc32")
        got = _chain_data_state(0, ds)
        if want is not None and int(want) != got:
            raise CheckpointCorrupt(
                f"{side}: data_state checksum mismatch (stored "
                f"{int(want):#010x}, recomputed {got:#010x}) — torn "
                f"sidecar or tampered cursor; resuming it would "
                f"silently diverge the sample stream")
    return ds
