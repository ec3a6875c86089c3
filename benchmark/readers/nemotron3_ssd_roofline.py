"""Share of the roofline the Mamba-2 mixers' selective scans reach in a
training step: the least time the chip could take for them
(lib/nemotron3_flops.py: the chunked scan's operations, and x, B, C, delta and
y once a pass in the compute type) over the device time of everything the
program ran under the scope ``mamba.scan`` (lib/phase_table.py), whatever
implements it: XLA's fusions today, a kernel tomorrow, found by scope and never
by a kernel's name.

The forward is counted as often as the trace shows it ran: once, and once more
where operations of the scope that the compiled text names as a rematerialized
block's replay (``rematted_computation`` in their ``op_name``) ran in the
stretch.  The time goes forward, replayed and backward apart on the earlier
line.  A program without the scope (the parent of the PR that added it) reads
nothing."""

import re

from lib import nemotron3_flops, peaks as pk, phase_table as pt

REPLAY = "rematted_computation"
_NAMED = re.compile(r'^\s+(?:ROOT\s+)?%?([^\s=]+)\s+=\s.*op_name="((?:[^"\\]|\\.)*)"')


def replayed_instructions(entry: str, scope: str) -> set:
    """Names of the compiled instructions of ``scope`` that are a replay."""
    try:
        from apex_tpu.observability import compilation
        text = compilation.get_ledger().compiled_text(entry) or ""
    except (ImportError, AttributeError):
        return set()
    found = set()
    for line in text.splitlines():
        if REPLAY in line and scope in line:
            m = _NAMED.match(line)
            if m and REPLAY in m.group(2) and scope in m.group(2):
                found.add(m.group(1))
    return found


def read(ctx, entry, scope="mamba.scan"):
    by_chip = pt.rows_by_chip(ctx, entry)
    model = ctx.facts.get("model", {})
    if by_chip is None or not ctx.peaks or "hybrid_override_pattern" not in model:
        return None
    rows = [(ev, backward) for ev, path, backward, _ in by_chip[min(by_chip)] if scope in path]
    if not rows:
        return None
    replay = replayed_instructions(entry, scope)
    ms = {"forward": 0.0, "replayed": 0.0, "backward": 0.0}
    for ev, backward in rows:
        kind = ("replayed" if pt.instruction_name(ev) in replay
                else "backward" if backward else "forward")
        ms[kind] += ev[2] / 1e6 / ctx.iterations
    forward_calls = 1.0 + (1.0 if ms["replayed"] > 0 else 0.0)
    f = ctx.facts
    fl, by = nemotron3_flops.scan_train_flops_bytes(
        model, f["rows_per_step"] // ctx.cell.chips, f["seq_len"], forward_calls)
    seconds = sum(ms.values()) / 1e3
    share = pk.roofline_share(fl, by, seconds, ctx.peaks)
    return {"value": share["share_pct"], "bound": share["bound"], "ms_per_step": seconds * 1e3,
            "ms_by_pass": ms, "forward_calls": forward_calls,
            "least_ms": share["share_pct"] / 100.0 * seconds * 1e3}
