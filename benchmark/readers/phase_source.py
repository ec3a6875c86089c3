"""How much of a step's phase table (lib/phase_table.py) does not rest on an
operation's own ``op_name``: the share (%) of the leaf operations' device time,
on the first chip, whose phase the program inferred from the compiled step's
dataflow; or, with ``mixed``, the share spent in fusions whose fused
instructions carry more than one top-level phase, where the fusion's phase is
its root's and the time could as well be the other's.  Read either before
believing that a per-layer number moved: work that moves moves the step too,
a label that moves moves these.

``entries``  ledger entries of a step; the first the ledger holds is read, so
             one file serves every training cell
``mixed``    report the mixed fusions' share instead of the inferred one

On an earlier line: per chip the share; ms per iteration by source (``own``,
``fused``, ``container``, ``sibling``, ``reader``, ``operand``, ``none``: they
sum to the busy time, and ``none`` is ``phase_ms``'s ``unscoped``) and the
twelve largest inferred operations with the phase each got; or the twelve
largest mixed fusions with the phases they hold, and ms by (root's phase,
other phase).  A program without ``instruction_phase_sources`` and
``fusion_phase_mix`` (the parent of the PR that added them) reports nothing.
"""

import collections

from lib import phase_table as pt, trace as tr

INFERRED = ("sibling", "reader", "operand")
NONE = "none"
SOURCES = ("own", "fused", "container") + INFERRED + (NONE,)


def _program(entries):
    """(entry, {instruction: source}, {fusion: {phase: count}}) of the first
    entry the ledger holds a compiled text of; None without the functions."""
    try:
        from apex_tpu.observability import compilation, phases
        sources_of, mix_of = phases.instruction_phase_sources, phases.fusion_phase_mix
    except (ImportError, AttributeError):
        return None
    for entry in entries:
        text = compilation.get_ledger().compiled_text(entry)     # kept since the first demand
        if text:
            return entry, sources_of(text), mix_of(text)
    return None


def _key(path, backward):
    return (path[0] + (".bwd" if backward else "")) if path else pt.UNSCOPED


def _largest_first(sums, n=None):
    return dict(sorted(sums.items(), key=lambda kv: -kv[1])[:n])


def _by_source(rows, sources, iterations):
    by_source, largest = dict.fromkeys(SOURCES, 0.0), collections.defaultdict(float)
    for ev, path, backward, joined in rows:
        ms = ev[2] / 1e6 / iterations
        # an operation the text lacks took a traced container's phase (lib/phase_table.py)
        source = NONE if not path else (sources.get(pt.instruction_name(ev), "container")
                                        if joined == pt.TEXT else "container")
        by_source[source] += ms
        if source in INFERRED:
            largest[f"{tr.op_label(ev)} -> {_key(path, backward)} ({source})"] += ms
    return by_source, _largest_first(largest, 12)


def _by_mix(rows, mix, iterations):
    total, largest, pairs = 0.0, collections.defaultdict(float), collections.defaultdict(float)
    for ev, path, backward, _ in rows:
        held = mix.get(pt.instruction_name(ev))
        if not held:
            continue
        ms, root = ev[2] / 1e6 / iterations, _key(path, backward)
        total += ms
        largest[f"{tr.op_label(ev)}: {root} of {'+'.join(sorted(held))}"] += ms
        for other in held:
            if other != root:
                pairs[f"{root}|{other}"] += ms
    return total, _largest_first(largest, 12), _largest_first(pairs)


def read(ctx, entries, mixed=False):
    if not ctx.ops or not ctx.stretch or not ctx.iterations:
        return None
    program = _program(entries)
    if program is None:
        return None
    entry, sources, mix = program
    by_chip = pt.rows_by_chip(ctx, entry)
    if by_chip is None:
        return None
    first, per_chip, said = min(by_chip), {}, {}
    for chip, rows in by_chip.items():
        whole = sum(ev[2] for ev, *_ in rows) / 1e6 / ctx.iterations
        if not whole:
            return None
        if mixed:
            ms, largest, pairs = _by_mix(rows, mix, ctx.iterations)
            if chip == first:
                said = {"mixed_fusion_ms": ms, "largest_mixed_ms": largest,
                        "by_root_and_other_ms": pairs, "mixed_fusions_in_text": len(mix)}
        else:
            by_source, largest = _by_source(rows, sources, ctx.iterations)
            ms = sum(by_source[s] for s in INFERRED)
            if chip == first:
                said = {"by_source_ms": by_source, "largest_inferred_ms": largest,
                        "leaf_ops_ms": whole}
        per_chip[chip] = 100.0 * ms / whole
    return {"value": per_chip[first], "per_chip": per_chip, "entry": entry, **said}
