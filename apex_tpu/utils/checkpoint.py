"""Checkpoint / resume for whole training states.

The reference relies on raw torch ``state_dict`` conventions and ships the
"option 2" pattern — fp32 masters and loss-scaler state saved alongside
the half model weights (fp16_utils/fp16_optimizer.py:298-359;
examples/imagenet/main_amp.py:170-185 epoch/best-prec resume).  SURVEY.md
§5 flags that the reference's new amp API *lacks* an ``amp.state_dict``;
apex_tpu closes that gap: ``amp.state_dict`` exists, and this module
persists any training-state pytree — params, optimizer state (masters
included, they are ordinary optimizer-state leaves here), BN running
stats, scaler state, step counters — to one atomic file.

Format: a single ``.npz`` holding every leaf keyed by its pytree keypath
string.  Restore is template-shaped: you pass the pytree you want filled
(built the same way as at save time), so no pickled treedefs are needed
and the format is stable across sessions and jax versions.

    ckpt.save_checkpoint(dir, step, {"params": params, "opt": opt_state,
                                     "bn": bn_state, "amp": amp_sd})
    state = ckpt.restore_checkpoint(dir, template)          # latest
    state = ckpt.restore_checkpoint(dir, template, step=7)  # specific
"""

from __future__ import annotations

import json
import os
import re
import tempfile
import time
import zlib
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["CheckpointCorrupt", "save_checkpoint", "restore_checkpoint",
           "latest_step", "available_steps", "latest_durable_step",
           "verify_checkpoint", "load_data_state", "tree_bytes",
           "tree_checksum", "record_checkpoint_io"]

_FMT = "ckpt_{step:08d}.npz"
_RE = re.compile(r"ckpt_(\d{8})\.npz$")

# reserved npz keys; never pytree keypaths (keystr always starts with a
# bracket/quote).  __checksum__ carries the snapshot's content
# checksum; __data_state__ carries the optional data-pipeline cursor
# blob (a JSON dict stored as uint8 bytes) so a snapshot names its
# exact sample-stream position — the preemption-safe resume contract.
# The data-state blob sits UNDER the checksum: it is part of the leaf
# dict the crc covers, so a torn or tampered cursor fails verification
# like any other leaf.
# __flat_order__ (a JSON dict stored like the data state, under the
# checksum too) says in which order the snapshot's amp flat buffers hold
# their leaves: {buffer length: "dtype" | "tree"} (``_FlatLayout.order``).
# The buffers themselves are bare arrays, and two orders can have one
# length; a snapshot without the entry predates it and holds tree order.
_CHECKSUM_KEY = "__checksum__"
_DATA_STATE_KEY = "__data_state__"
_FLAT_ORDER_KEY = "__flat_order__"


class CheckpointCorrupt(RuntimeError):
    """A snapshot failed content verification (torn/partial write, bit
    rot, truncation).  Restore raises this instead of silently loading
    garbage; the recovery controller catches it and falls back to the
    previous durable snapshot (``latest_durable_step``)."""

# seconds; local-disk npz snapshots up to multi-minute sharded
# TensorStore writes
_CKPT_BUCKETS = (0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
                 30.0, 60.0, 120.0, 300.0)


def tree_bytes(tree: Any) -> int:
    """In-memory bytes of one state tree's leaves (what a snapshot
    persists, pre-compression) — the ``checkpoint_snapshot_bytes``
    gauge."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        n = getattr(leaf, "nbytes", None)
        total += int(n) if n is not None else np.asarray(leaf).nbytes
    return total


def record_checkpoint_io(op: str, seconds: float, step=None,
                         nbytes: Optional[int] = None,
                         path: Optional[str] = None,
                         async_save: bool = False,
                         registry=None, ring=None) -> None:
    """Checkpoint telemetry shared by the npz and Orbax paths: fold
    one save/restore into the metrics registry (latency histogram,
    op counter, snapshot-bytes gauge) and — for saves — append the
    ``checkpoint_saved`` flight-ring event the training-run
    supervisor's progress watermark consumes (a run that is writing
    checkpoints is making durable progress).  ``op`` is ``"save"`` or
    ``"restore"``; defaults resolve the process registry/ring per
    call, the same rule as every other producer."""
    if op not in ("save", "restore"):
        raise ValueError(f"op must be 'save' or 'restore', got {op!r}")
    from ..observability import flightrec
    from ..observability.metrics import get_registry
    reg = registry if registry is not None else get_registry()
    reg.histogram(f"checkpoint_{op}_seconds",
                  help=f"wall seconds per checkpoint {op}",
                  buckets=_CKPT_BUCKETS).observe(float(seconds))
    reg.counter(f"checkpoint_{op}s_total").inc()
    if nbytes is not None:
        reg.gauge("checkpoint_snapshot_bytes",
                  help="leaf bytes of the last checkpointed state tree"
                  ).set(float(nbytes))
    if op == "save":
        flightrec.resolve(ring).append(
            "checkpoint_saved",
            step=int(step) if step is not None else None,
            bytes=nbytes, path=path, async_save=bool(async_save),
            duration_s=round(float(seconds), 6))


def tree_checksum(leaves: dict) -> int:
    """Order-independent-by-construction content checksum of a leaf
    dict (``{keypath: np.ndarray}``): crc32 chained over the sorted
    keys, each leaf's dtype/shape, and its raw bytes.  Shared by the
    npz path (embedded under ``__checksum__``) and the Orbax path
    (sidecar file) so one verifier serves both."""
    crc = 0
    for key in sorted(leaves):
        arr = np.asarray(leaves[key])
        crc = zlib.crc32(key.encode(), crc)
        crc = zlib.crc32(str(arr.dtype).encode(), crc)
        crc = zlib.crc32(str(tuple(arr.shape)).encode(), crc)
        crc = zlib.crc32(np.ascontiguousarray(arr).tobytes(), crc)
    return crc & 0xFFFFFFFF


def _leaf_dict(tree: Any) -> dict:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = {}
    for path, leaf in flat:
        key = jax.tree_util.keystr(path)
        if key in out:
            raise ValueError(f"duplicate keypath {key!r}")
        arr = np.asarray(leaf)
        if arr.dtype.kind == "V" or arr.dtype.name in ("bfloat16",):
            # npz has no bfloat16/fp8; fp32 holds them exactly, and restore
            # casts back to the template dtype
            arr = np.asarray(leaf, np.float32)
        out[key] = arr
    return out


def save_checkpoint(ckpt_dir: str, step: int, tree: Any,
                    keep: Optional[int] = None,
                    data_state: Optional[dict] = None) -> str:
    """Write ``tree`` for ``step``; atomic (write-temp + rename).  With
    ``keep``, retain only the newest ``keep`` checkpoints.
    ``data_state`` is an optional JSON-serializable dict (e.g.
    ``DataLoader.state_dict()``) persisted alongside the tree under the
    content checksum, so the snapshot names its exact data cursor;
    read it back with :func:`load_data_state`."""
    if keep is not None and keep < 1:
        raise ValueError(f"keep must be >= 1, got {keep}")
    os.makedirs(ckpt_dir, exist_ok=True)
    t0 = time.perf_counter()
    leaves = _leaf_dict(tree)
    for reserved in (_CHECKSUM_KEY, _DATA_STATE_KEY, _FLAT_ORDER_KEY):
        if reserved in leaves:
            raise ValueError(f"{reserved!r} is a reserved key")
    if data_state is not None:
        blob = json.dumps(data_state, sort_keys=True).encode()
        leaves[_DATA_STATE_KEY] = np.frombuffer(blob, np.uint8)
    orders = flat_orders(tree)
    if orders:
        blob = json.dumps(orders, sort_keys=True).encode()
        leaves[_FLAT_ORDER_KEY] = np.frombuffer(blob, np.uint8)
    # content checksum over exactly the arrays being written: restore
    # recomputes it from what it read, so a torn/partial write (or
    # later bit rot) can never load silently.  Because the checksum is
    # computed from the data in hand and the file lands by atomic
    # rename, the checkpoint_saved event below only ever names a
    # snapshot that verifies.
    leaves[_CHECKSUM_KEY] = np.uint32(tree_checksum(leaves))
    path = os.path.join(ckpt_dir, _FMT.format(step=step))
    fd, tmp = tempfile.mkstemp(dir=ckpt_dir, suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            np.savez(f, **leaves)
        os.replace(tmp, path)
    except BaseException:
        if os.path.exists(tmp):
            os.unlink(tmp)
        raise
    # telemetry only after the rename: a failed write must not emit a
    # checkpoint_saved event the supervisor would count as progress
    record_checkpoint_io("save", time.perf_counter() - t0, step=step,
                         nbytes=tree_bytes(tree), path=path)
    if keep is not None:
        for s in available_steps(ckpt_dir)[:-keep]:
            os.unlink(os.path.join(ckpt_dir, _FMT.format(step=s)))
    return path


def available_steps(ckpt_dir: str) -> list:
    steps = []
    if os.path.isdir(ckpt_dir):
        for name in os.listdir(ckpt_dir):
            m = _RE.match(name)
            if m:
                steps.append(int(m.group(1)))
    return sorted(steps)


def latest_step(ckpt_dir: str) -> Optional[int]:
    steps = available_steps(ckpt_dir)
    return steps[-1] if steps else None


def _load_verified(path: str) -> dict:
    """Read one snapshot and verify its content checksum; raises
    :class:`CheckpointCorrupt` on a torn/truncated/corrupted file.
    Pre-checksum snapshots (no ``__checksum__`` entry) load as-is —
    they predate verification and are trusted like before."""
    import zipfile
    try:
        with np.load(path) as data:
            stored = dict(data)
    except (OSError, ValueError, EOFError, KeyError,
            zipfile.BadZipFile) as e:
        # a torn npz fails in the zip layer (BadZipFile on a truncated
        # central directory, KeyError on a missing member) or in the
        # per-array header parse — all corruption
        raise CheckpointCorrupt(f"{path}: unreadable snapshot ({e})")
    want = stored.pop(_CHECKSUM_KEY, None)
    if want is not None:
        got = tree_checksum(stored)
        if int(want) != got:
            raise CheckpointCorrupt(
                f"{path}: content checksum mismatch (stored "
                f"{int(want):#010x}, recomputed {got:#010x}) — torn "
                f"write or bit rot; fall back to an earlier snapshot")
    return stored


def verify_checkpoint(ckpt_dir: str, step: int) -> None:
    """Verify one snapshot's content checksum without restoring it;
    raises :class:`CheckpointCorrupt` (or ``FileNotFoundError``)."""
    path = os.path.join(ckpt_dir, _FMT.format(step=step))
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    _load_verified(path)


def load_data_state(ckpt_dir: str,
                    step: Optional[int] = None) -> Optional[dict]:
    """Read the snapshot's data-pipeline cursor blob (what
    ``save_checkpoint(..., data_state=...)`` persisted), verified under
    the same content checksum as the tree.  ``None`` when the snapshot
    carries no data state (it predates the field, or the run had no
    checkpointable pipeline)."""
    if step is None:
        step = latest_step(ckpt_dir)
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {ckpt_dir!r}")
    path = os.path.join(ckpt_dir, _FMT.format(step=step))
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    stored = _load_verified(path)
    blob = stored.get(_DATA_STATE_KEY)
    if blob is None:
        return None
    return json.loads(np.asarray(blob, np.uint8).tobytes().decode())


def latest_durable_step(ckpt_dir: str) -> Optional[int]:
    """Newest snapshot step that VERIFIES — the recovery controller's
    resume-point oracle: torn snapshots are skipped (newest first)
    until one passes its content check; ``None`` when none do."""
    for step in reversed(available_steps(ckpt_dir)):
        try:
            verify_checkpoint(ckpt_dir, step)
            return step
        except CheckpointCorrupt:
            continue
    return None


def _flat_layouts(tree: Any) -> dict:
    """``{buffer length: layout}`` of the un-sharded amp flat states in
    ``tree`` (masters; the inner optimizer's moments have the same
    length)."""
    from ..amp._process_optimizer import FlatMasters
    nodes = jax.tree_util.tree_leaves(
        tree, is_leaf=lambda n: isinstance(n, FlatMasters))
    return {int(n.buf.shape[0]): n.layout for n in nodes
            if isinstance(n, FlatMasters) and n.layout.zero_axis is None}


def flat_orders(tree: Any) -> dict:
    """``{str(buffer length): order}`` of the amp flat buffers in
    ``tree``: what a snapshot of it says under ``__flat_order__``."""
    return {str(n): lay.order for n, lay in _flat_layouts(tree).items()}


def _place_flat(arr: np.ndarray, layout, saved_order: str, key: str
                ) -> np.ndarray:
    """A stored flat buffer as ``layout`` keeps it.  A snapshot in tree
    order (any from before the layout kept its leaves by dtype; at the
    logical length from before PR 25, at the block-aligned one since) is
    moved leaf by leaf to the layout's offsets; the zeros no leaf owns
    are made here.  What cannot be placed is refused."""
    from ..ops.pallas_common import aligned_len
    if saved_order != "tree":
        if saved_order != layout.order:
            raise ValueError(
                f"{key!r}: the snapshot holds its flat buffers in "
                f"{saved_order!r} order and the template's layout keeps "
                f"{layout.order!r} order: it cannot be placed (build the "
                f"template as the saved state was built)")
        return arr
    if arr.shape[0] not in (layout.total, aligned_len(layout.total)):
        return arr              # not this layout's: the shape check names it
    out = np.zeros((layout.storage,), arr.dtype)
    at = 0
    for off, n in zip(layout.offsets, layout.sizes):
        out[off:off + n] = arr[at:at + n]
        at += n
    return out


def restore_checkpoint(ckpt_dir: str, template: Any,
                       step: Optional[int] = None) -> Any:
    """Return ``template`` with every leaf replaced by the stored value
    (cast to the template leaf's dtype, shapes must match).  ``step=None``
    loads the newest checkpoint; raises FileNotFoundError if none and
    :class:`CheckpointCorrupt` when the snapshot fails its content
    checksum (torn write)."""
    if step is None:
        step = latest_step(ckpt_dir)
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {ckpt_dir!r}")
    path = os.path.join(ckpt_dir, _FMT.format(step=step))
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    t0 = time.perf_counter()
    stored = _load_verified(path)
    stored.pop(_DATA_STATE_KEY, None)   # read via load_data_state
    blob = stored.pop(_FLAT_ORDER_KEY, None)
    orders = ({} if blob is None else
              json.loads(np.asarray(blob, np.uint8).tobytes().decode()))
    flat, treedef = jax.tree_util.tree_flatten_with_path(template)
    layouts = _flat_layouts(template)
    out = []
    for kp, leaf in flat:
        key = jax.tree_util.keystr(kp)
        if key not in stored:
            raise KeyError(
                f"checkpoint {path} has no entry for {key!r} — template "
                "structure does not match the saved state")
        arr = stored[key]
        if (arr.ndim == 1 and getattr(leaf, "ndim", None) == 1
                and leaf.shape[0] in layouts):
            arr = _place_flat(arr, layouts[leaf.shape[0]],
                              orders.get(str(arr.shape[0]), "tree"), key)
        if hasattr(leaf, "shape") and tuple(arr.shape) != tuple(leaf.shape):
            raise ValueError(
                f"shape mismatch for {key!r}: checkpoint {arr.shape} vs "
                f"template {leaf.shape}")
        dtype = getattr(leaf, "dtype", arr.dtype)
        out.append(jnp.asarray(arr, dtype))
    restored = jax.tree_util.tree_unflatten(treedef, out)
    record_checkpoint_io("restore", time.perf_counter() - t0,
                         step=step, nbytes=tree_bytes(restored),
                         path=path)
    return restored
