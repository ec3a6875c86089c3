"""Device time by the phase the program gave each operation.

The program opens ``jax.named_scope`` of a fixed vocabulary where the work is
written (``apex_tpu/observability/phases.py``), the compiled step's text keeps
the scope in each instruction's ``op_name``, and a trace event keeps the
instruction's name with its number (``%fusion.123 = ...``).  The join is by
that name: the program's compilation ledger gives the optimized HLO text of an
entry, the program's ``instruction_phases`` reads it, and this file sums the
trace's leaf operations by the path found.  A program without the ledger
method or the function (the parent of the PR that added them) gives ``None``
everywhere, and the readers then report nothing.
"""

from __future__ import annotations

import re
import time
from typing import Dict, List, Optional, Sequence, Tuple

from lib import trace as tr

UNSCOPED = "unscoped"
NAME = re.compile(r"^%(\S+) = ")
_programs: Dict[str, Optional[dict]] = {}
text_seconds: Dict[str, float] = {}      # entry -> what asking the program for its text cost
_attributed: Dict[tuple, dict] = {}

# (event, phase path, is_backward, where the phase came from)
Row = Tuple[list, tuple, bool, str]
TEXT, CONTAINER, NOWHERE = "text", "container", "nowhere"


def instruction_name(ev: list) -> Optional[str]:
    m = NAME.match(ev[0])
    return m.group(1) if m else None


def program_phases(entry: str) -> Optional[dict]:
    """{instruction name: (phase path, is_backward)} of the ledger entry's
    compiled program, from the program's own functions; None without them."""
    if entry not in _programs:
        phases = None
        try:
            from apex_tpu.observability import compilation, phases as program
            began = time.perf_counter()
            text = compilation.get_ledger().compiled_text(entry)
            text_seconds[entry] = time.perf_counter() - began
            if text:
                phases = program.instruction_phases(text)
        except (ImportError, AttributeError):
            phases = None
        _programs[entry] = phases
    return _programs[entry]


def attribute(events: Sequence[list], phases: dict, lo: float, hi: float) -> List[Row]:
    """The leaf operations wholly inside [lo, hi) with their phase, and where it
    came from: the program's text (``TEXT``); or, for an event whose instruction
    the text does not hold, the innermost ``while``/``conditional``/``call`` event
    that spans it (``CONTAINER``); or ``NOWHERE``.  The join is by instruction
    name between the executable that ran and the text the program handed out, so
    anything but ``TEXT`` says the two are not one program."""
    rows, open_containers = [], []
    for ev in sorted(events, key=lambda e: (e[1], -e[2])):
        start, end = ev[1], ev[1] + ev[2]
        while open_containers and open_containers[-1][0] <= start:
            open_containers.pop()
        found, source = phases.get(instruction_name(ev)), TEXT
        if found is None and open_containers:
            found, source = open_containers[-1][1], CONTAINER
        elif found is None:
            found, source = ((), False), NOWHERE
        if tr.is_container(ev):
            open_containers.append((end, found))
        elif start >= lo and end <= hi:
            rows.append((ev, found[0], found[1], source))
    return rows


def rows_by_chip(ctx, entry: str) -> Optional[Dict[int, List[Row]]]:
    """chip -> attributed leaf operations of the traced stretch (kept per run)."""
    if not ctx.ops or not ctx.stretch or not ctx.iterations:
        return None
    phases = program_phases(entry)
    if phases is None:
        return None
    key = (id(ctx.ops), entry)
    if key not in _attributed:
        _attributed.clear()
        _attributed[key] = {chip: attribute(ev, phases, *ctx.stretch)
                            for chip, ev in ctx.ops.items()}
    return _attributed[key]


def matches(path: tuple, within: Sequence[str]) -> bool:
    """Whether any scope of ``path`` is one of ``within`` (``optim.*`` takes
    every scope with that prefix)."""
    for want in within:
        if want.endswith("*"):
            if any(p.startswith(want[:-1]) for p in path):
                return True
        elif want in path:
            return True
    return False


def table(rows: Sequence[Row], iterations: int) -> Dict[str, float]:
    """ms per iteration by top-level phase (backward apart), and ``unscoped``."""
    out: Dict[str, float] = {}
    for ev, path, backward, _ in rows:
        key = (path[0] + (".bwd" if backward else "")) if path else UNSCOPED
        out[key] = out.get(key, 0.0) + ev[2] / 1e6 / iterations
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))
