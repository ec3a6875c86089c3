"""Device milliseconds per harness iteration of the operations the program
put under given scopes (lib/phase_table.py), on the first chip; the per-chip
figures, and what else the parameters ask for, go on an earlier line.

``within``      scopes of the program's vocabulary; an operation counts when
                any scope of its phase path is among them (``optim.*``: prefix)
``not_kinds``   HLO op kinds left out (``custom-call``: the Mosaic kernels)
``direction``   ``forward`` or ``backward`` only
``async_spans`` also count asynchronous collectives of these scopes from start
                to done (the union with the operations, not the sum)
``kinds``       [[label, regex on the module path], ...]: ms by module kind
``unscoped``    report instead the share (%) of the leaf operations' time that
                has no phase, and log the whole table by top-level phase, the
                seconds the program took to hand out its compiled text, and
                the health of the join by instruction name: ``not_in_text_ms``
                (operations the text does not hold; 0 when the text is of the
                executable that ran) and how much of it took a spanning
                container's phase (``by_container_ms``)
"""

import re

from lib import phase_table as pt, trace as tr


def _selected(rows, within, not_kinds, direction):
    for ev, path, backward, _ in rows:
        if direction and backward != (direction == "backward"):
            continue
        if ev[3].get("kind") in not_kinds or not pt.matches(path, within):
            continue
        yield ev, path


def _largest_first(sums, n=None):
    return dict(sorted(sums.items(), key=lambda kv: -kv[1])[:n])


def _add(sums, key, ms):
    sums[key] = sums.get(key, 0.0) + ms


def _async_intervals(ctx, chip, entry, within):
    """Start-to-done intervals of the chip's asynchronous collectives of these scopes."""
    phases, (lo, hi) = pt.program_phases(entry), ctx.stretch
    for ev in tr.async_spans(ctx.trace, chip):
        found = phases.get(pt.instruction_name(ev))
        if found and pt.matches(found[0], within) and ev[1] >= lo and ev[1] + ev[2] <= hi:
            yield ev[1], ev[1] + ev[2]


def _whole_table(ctx, by_chip, entry):
    first = min(by_chip)
    tables = {chip: pt.table(rows, ctx.iterations) for chip, rows in by_chip.items()}
    sums = {chip: sum(t.values()) for chip, t in tables.items()}
    if not sums[first]:
        return None
    busy = {chip: tr.busy(ctx.ops[chip], *ctx.stretch) / 1e6 / ctx.iterations for chip in by_chip}
    ops, joined = {}, {pt.CONTAINER: 0.0, pt.NOWHERE: 0.0}
    for ev, path, _, source in by_chip[first]:
        ms = ev[2] / 1e6 / ctx.iterations
        if not path:
            _add(ops, tr.op_label(ev), ms)
        if source != pt.TEXT:
            joined[source] += ms
    return {"value": 100.0 * tables[first].get(pt.UNSCOPED, 0.0) / sums[first],
            "phase_ms": tables, "phase_sum_ms": sums, "busy_ms": busy,
            "unscoped_ops_ms": _largest_first(ops, 12),
            "not_in_text_ms": joined[pt.CONTAINER] + joined[pt.NOWHERE],
            "by_container_ms": joined[pt.CONTAINER],
            "compiled_text_s": pt.text_seconds.get(entry)}


def read(ctx, entry, within=(), not_kinds=(), direction=None, async_spans=False, kinds=None,
         unscoped=False):
    by_chip = pt.rows_by_chip(ctx, entry)
    if by_chip is None:
        return None
    if unscoped:
        return _whole_table(ctx, by_chip, entry)
    per_chip = {}
    for chip, rows in by_chip.items():
        picked = [(ev[1], ev[1] + ev[2])
                  for ev, _ in _selected(rows, within, not_kinds, direction)]
        if async_spans:
            picked = tr.merge(picked + list(_async_intervals(ctx, chip, entry, within)))
        per_chip[chip] = tr.total(picked) / 1e6 / ctx.iterations
    first = min(by_chip)
    scopes, ops, by_kind = {}, {}, {}
    for ev, path in _selected(by_chip[first], within, not_kinds, direction):
        ms = ev[2] / 1e6 / ctx.iterations
        module = path[path.index("model") + 1] if "model" in path[:-1] else None
        _add(scopes, "/".join(p for p in path if p is not module), ms)
        _add(ops, tr.op_label(ev), ms)
        if kinds:
            _add(by_kind, next((k for k, rx in kinds if re.search(rx, module or path[0])),
                               "other"), ms)
    out = {"value": per_chip[first], "per_chip": per_chip,
           "by_scope_ms": _largest_first(scopes), "by_op_ms": _largest_first(ops, 8)}
    if kinds:
        out["by_module_kind_ms"] = _largest_first(by_kind)
    return out
