"""The phase vocabulary of a step, and the phase of a compiled instruction.

The hot path opens ``jax.named_scope`` of exactly the names in
:data:`PHASES` where the work is written (amp, the optimizers, DDP,
``nn.Module``, the example's loss head, the paged engine's tick).  A scope
changes HLO metadata and nothing else: every instruction the scoped
python emitted carries the scope in its ``op_name``
(``jit(step)/shard_map/amp.update/cond/branch_0_fun/optim.adam/mul``), and
autodiff wraps the outermost scope of a differentiated region
(``jvp(model)`` forward, ``transpose(jvp(model))`` backward).

A device trace names each executed instruction by its HLO name
(``%fusion.123``) and drops the metadata, so the phase of a trace event is
found by instruction name in the optimized HLO text of the same program
(``CompilationLedger.compiled_text(entry)``, or any
``compiled.as_text()``): :func:`instruction_phases`.

Stdlib only: trace readers import it without jax.
"""

from __future__ import annotations

import functools
import heapq
import re
from typing import Dict, List, Optional, Tuple

__all__ = ["PHASES", "SOURCES", "phase_of_op_name", "instruction_phases",
           "instruction_phase_sources", "fusion_phase_mix"]

# scope -> where it is opened
PHASES = (
    "amp.scale_loss",      # amp/handle.py: loss * scale, and the divide back
    "amp.pack",            # AmpOptimizer.step: layout.pack / pack_grads
    "amp.unscale",         # scaler.unscale as called from step, or the
                           # finite read where the kernel unscales
    "amp.scaler_update",   # scaler.update as called from step
    "amp.update",          # the apply-or-skip lax.cond and its do_update
    "amp.rebuild",         # layout.rebuild / master -> model copy (in amp.update)
    "amp.grad_norm",       # the norm at the end of step
    "optim.adam",          # FusedAdam.step: kernel and what pads/reshapes it
    "optim.lamb",          # FusedLAMB.step
    "optim.lion",          # FusedLion.step
    "ddp.pack",            # bucket concatenate / cast / predivide
    "ddp.reduce",          # psum / hierarchical / chunked reduce
    "ddp.unpack",          # post-divide, cast, slices back
    "model",               # nn.apply root; the module path follows it
    "loss",                # the example's loss head
    "paged.gather",        # PagedEngine tick: _gather_dense
    "paged.scatter",       # PagedEngine tick: _scatter_cols
    "paged.attend",        # PagedEngine tick: model.decode_chunk
    # parallel/expert_parallel.py, the sorted dispatch (inside ``model``)
    "moe.route",           # router matmul, scores, top-k, gate weights
    "moe.dispatch",        # sort of the assignments, group sizes, row gather
    "moe.experts",         # grouped products and the shared expert
    "moe.combine",         # weighted scatter-add back to the tokens
    # transformer/short_conv.py, the gated short convolution (inside ``model``)
    "conv.in_proj",        # u W_in: the three gates' projection
    "conv.mix",            # B * z, the taps along the sequence, C * c
    "conv.out_proj",       # (C * c) W_out
    # transformer/mamba2.py, the Mamba-2 mixer (inside ``model``)
    "mamba.in_proj",       # u W_in: z, x, B, C and dt in one projection
    "mamba.conv",          # the causal taps over x, B, C, their bias, silu
    "mamba.scan",          # softplus(dt), the decays and the chunked scan
    "mamba.gate_norm",     # y * silu(z) and the RMSNorm over each group
    "mamba.out_proj",      # y W_out
    # transformer/mla.py, latent attention (inside ``model``; the flash
    # kernels between ``mla.rope`` and ``mla.o_proj`` are their own names)
    "mla.q_proj",          # u W_q, the two parts of the score heads
    "mla.kv_down",         # u W_kva: the latent and the shared key head
    "mla.kv_norm",         # RMSNorm over the latent
    "mla.kv_up",           # the latent to every head's k (no position) and v
    "mla.rope",            # the rotation of q's rope parts and of the key head
    "mla.o_proj",          # [o_1 .. o_H] W_o
    # models/laguna.py, attention with ``qk_norm``
    "attn.qk_norm",        # RMSNorm over each head of q and of k, before RoPE
    # models/laguna.py, the looped stack (``total_ut_steps`` > 1)
    "loop",                # the scan over the passes (inside ``model``): the
                           # layers' module paths follow it
    "loop.norm",           # the final norm at the end of every pass
    "loss.head",           # the fused head on every pass's state (in ``loss``)
    "loss.exit",           # exit gate, exit distribution, the weighted loss
)
MODEL = "model"

_VOCAB = frozenset(PHASES)
# op_name components jax itself puts between a module scope and the primitive
_JAX_STRUCTURE = frozenset((
    "cond", "while", "body", "scan", "checkpoint", "remat", "pallas_call",
    "shard_map", "closed_call", "core_call", "custom_vjp_call",
    "custom_jvp_call", "custom_vjp_call_jaxpr", "custom_lin",
    "rematted_computation"))
_PLAIN = re.compile(r"[A-Za-z0-9_]+$")
_WRAPPED = re.compile(r"(?:[\w.\-]+\()*([^()]*)\)*$")

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s*\(.*\)\s*->.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%?([^\s=]+)\s+=\s")
_KIND = re.compile(r"\s([a-z][a-z\-]*)\(")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLEE = re.compile(r"(?:to_apply|body|condition|true_computation|"
                     r"false_computation)=%?([^\s,}]+)")
_FUSED = re.compile(r"calls=%?([^\s,}]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_OPERAND = re.compile(r"%([^\s,()]+)")

Phase = Tuple[Tuple[str, ...], bool]
# where an instruction's phase came from (instruction_phase_sources)
OWN, FUSED, CONTAINER, SIBLING, READER, OPERAND, NO_SOURCE = SOURCES = (
    "own", "fused", "container", "sibling", "reader", "operand", "none")


def phase_of_op_name(op_name: str) -> Phase:
    """``(phase path, is_backward)`` of one ``op_name``: the vocabulary's
    scopes found in it, outermost first, with the module path that follows
    ``model`` kept whole as one element (``("model",
    "BertForPretraining/bert/3/attention/qkv")``); backward when a scope
    sits inside ``transpose(``.  ``((), False)`` when no scope is found.  A
    scope opened between ``model`` and the module path (``loop``: the scan a
    stack of layers runs in) follows the module path it encloses:
    ``("model", "layers/3/mlp", "loop")``."""
    # the compiler joins the names of instructions it merged with ";"
    parts = op_name.split(";")[0].split("/")
    path, last, i = [], -1, 0
    while i < len(parts):
        base = _WRAPPED.match(parts[i]).group(1)
        if base in _VOCAB:
            if not (path and path[-1] == base):
                path.append(base)
            last = i
            if base == MODEL:
                modules, around = [], []
                # the last component is the primitive, never a module
                while i + 1 < len(parts) - 1:
                    nxt = parts[i + 1]
                    inner = _WRAPPED.match(nxt).group(1)
                    if not modules and (nxt in _JAX_STRUCTURE
                                        or inner == MODEL):
                        # a rematerialized block's backward: transpose(
                        # jvp(model))/jvp(model)/checkpoint/<modules>
                        i += 1
                        continue
                    if not modules and inner in _VOCAB:
                        # a scope around the modules (the looped stack's)
                        if inner not in around:
                            around.append(inner)
                        i += 1
                        last = i
                        continue
                    if not (_PLAIN.match(nxt) and nxt not in _VOCAB
                            and nxt not in _JAX_STRUCTURE
                            and not nxt.startswith("branch_")):
                        break
                    i += 1
                    modules.append(nxt)
                if modules:
                    path.append("/".join(modules))
                path.extend(around)
        i += 1
    backward = any("transpose(" in p for p in parts[:last + 1])
    return tuple(path), backward


# what a walk steps over and a chain runs through: these execute nothing
_NO_WORK = frozenset(("bitcast", "get-tuple-element", "tuple", "optimization-barrier",
                      "parameter"))
_JOINS = frozenset(("dynamic-update-slice", "concatenate", "pad"))
_MOVES = frozenset(("copy", "transpose"))
# an asynchronous move's time is its ``-done``'s; its ``-start`` only issues it
_ASYNC_MOVES = ("-update", "-done")
_UNSCOPED: "Phase" = ((), False)


class _Module:
    """One parse of an optimized HLO module's text: per instruction its op
    kind, operands, readers (both in program order) and the phase of its own
    ``op_name``; per computation its instructions, root and caller."""

    def __init__(self, hlo_text: str):
        self.own: Dict[str, Optional[Phase]] = {}
        self.kind: Dict[str, str] = {}
        self.operands: Dict[str, List[str]] = {}
        self.readers: Dict[str, List[str]] = {}
        self.position: Dict[str, int] = {}       # program order, over the whole text
        self.computation_of: Dict[str, str] = {}
        self.members: Dict[str, List[str]] = {}  # computation -> its instructions
        self.root: Dict[str, str] = {}           # computation -> its root
        self.caller_of: Dict[str, str] = {}      # computation -> calling instruction
        self.fused_of: Dict[str, str] = {}       # fusion instruction -> its computation
        self.parameter: Dict[str, int] = {}      # parameter instruction -> its number
        current = None
        for line in hlo_text.splitlines():
            m = _INSTRUCTION.match(line)
            if m is None:
                c = _COMPUTATION.match(line)
                if c:
                    current = c.group(1)
                continue
            name = m.group(2)
            op = _OP_NAME.search(line)
            self.own[name] = phase_of_op_name(op.group(1)) if op else None
            self.computation_of[name] = current
            self.position[name] = len(self.position)
            self.members.setdefault(current, []).append(name)
            if m.group(1):
                self.root[current] = name
            k = _KIND.search(line, m.end() - 1)
            kind, reads = (k.group(1), _operands(line, k.end())) if k else ("", "")
            self.kind[name] = kind
            if kind == "parameter":
                self.parameter[name] = int(reads) if reads.isdigit() else -1
                reads = ""
            self.operands[name] = [r for r in _OPERAND.findall(reads) if r in self.own]
            self.readers[name] = []
            for read in dict.fromkeys(self.operands[name]):
                if read != name:
                    self.readers[read].append(name)
            callees = _CALLEE.findall(line)
            fused = _FUSED.search(line)
            if fused:
                self.fused_of[name] = fused.group(1)
                callees.append(fused.group(1))
            for group in _BRANCHES.findall(line):
                callees += [b.strip().lstrip("%") for b in group.split(",")]
            for callee in callees:
                self.caller_of.setdefault(callee, name)

    # -- the rules that read metadata (PR 24) ---------------------------------------
    def by_metadata(self, name: str) -> Tuple[Phase, str]:
        """Own ``op_name``; a fusion's fused instructions, nearest the root;
        the enclosing ``while`` / ``conditional`` / ``call`` / fusion."""
        phase = self.own[name]
        if phase is not None:
            return phase, (OWN if phase[0] else NO_SOURCE)
        phase = next((self.own[i] for i in reversed(self.members.get(self.fused_of.get(name), ()))
                      if self.own[i] is not None), None)
        if phase is not None:
            return phase, (FUSED if phase[0] else NO_SOURCE)
        seen, at = {name}, name
        while True:
            at = self.caller_of.get(self.computation_of.get(at))
            if at is None or at in seen or at not in self.own:
                return _UNSCOPED, NO_SOURCE
            seen.add(at)
            if self.own[at] is not None:
                return self.own[at], (CONTAINER if self.own[at][0] else NO_SOURCE)

    # -- the rules that read dataflow (PR 38) ---------------------------------------
    def writes_in_place(self, name: str) -> bool:
        """An in-place writer or join, bare or as the root of a fusion."""
        return self.kind[name] in _JOINS or self.kind.get(self._fused_root(name)) in _JOINS

    def _fused_root(self, name: str) -> Optional[str]:
        return self._through_no_work(self.root.get(self.fused_of.get(name)))

    def buffer_of(self, name: str) -> Optional[str]:
        """The instruction whose result the writer ``name`` writes into: its
        operand 0, for a fusion the operand its fused root takes as operand 0.
        None where the buffer is made inside the fusion (a chain's first link)."""
        if self.kind[name] in _JOINS:
            return self._through_no_work(next(iter(self.operands[name]), None))
        root = self._fused_root(name)
        inside = self._through_no_work(next(iter(self.operands[root]), None))
        number = self.parameter.get(inside, -1)
        if 0 <= number < len(self.operands[name]):
            return self._through_no_work(self.operands[name][number])
        return None

    def _through_no_work(self, name: Optional[str]) -> Optional[str]:
        """``name``, or what it is a view of (operand 0 of each ``bitcast`` ..)."""
        seen = set()
        while (name is not None and name not in seen and self.operands[name]
               and self.kind[name] in _NO_WORK):
            seen.add(name)
            name = self.operands[name][0]
        return name

    def chain_step(self, name: str, forwards: bool) -> Optional[str]:
        """The writer before ``name`` along the chain over its buffer (the
        one whose result it writes into), or the one after it (the first
        reader that writes ``name``'s result in place)."""
        if not forwards:
            at = self.buffer_of(name)
            return at if at is not None and self.writes_in_place(at) else None
        return next((r for r in self._readers_through_no_work(name)
                     if self.writes_in_place(r) and self.buffer_of(r) == name), None)

    def _readers_through_no_work(self, name: str) -> List[str]:
        out, todo, seen = [], [name], {name}
        while todo:
            for r in self.readers[todo.pop()]:
                if r in seen:
                    continue
                seen.add(r)
                if self.kind[r] in _NO_WORK and self.operands[r][0] in seen:
                    todo.append(r)
                else:
                    out.append(r)
        return sorted(out, key=self.position.__getitem__)

    def first_reader(self, name: str, phases: Dict[str, Phase]) -> Optional[Phase]:
        """The phase of the first instruction in program order that reads
        ``name``, through instructions without a phase."""
        seen, heap = {name}, []

        def push(of):
            for r in self.readers[of]:
                if r not in seen:
                    seen.add(r)
                    heapq.heappush(heap, (self.position[r], r))
        push(name)
        while heap:
            _, at = heapq.heappop(heap)
            if phases[at][0]:
                return phases[at]
            push(at)
        return None

    def first_operand(self, name: str, phases: Dict[str, Phase]) -> Optional[Phase]:
        """The phase of the first operand that has one, each operand looked
        through (its own operands, in order) while it has none."""
        seen, todo = {name}, list(reversed(self.operands[name]))
        while todo:
            at = todo.pop()
            if at in seen:
                continue
            seen.add(at)
            if phases[at][0]:
                return phases[at]
            todo.extend(reversed(self.operands[at]))
        return None


def _operands(line: str, start: int) -> str:
    """What stands between the parenthesis at ``start - 1`` and its match."""
    end = line.find(")", start)
    if "(" not in line[start:end]:
        return line[start:end]
    depth = 1
    for end in range(start, len(line)):
        depth += (line[end] == "(") - (line[end] == ")")
        if not depth:
            break
    return line[start:end]


def _nearest_sibling(mod: _Module, phases: Dict[str, Phase], name: str,
                     memo: Tuple[dict, dict]) -> Optional[Phase]:
    """The phase of the nearest writer that has one along the chain over
    ``name``'s buffer: backwards first, then forwards; ``memo`` keeps what
    every link walked over found, each way, so a chain is walked once."""
    for forwards in (False, True):
        trail, at, phase = [], name, None
        while at is not None and at not in trail:
            if at in memo[forwards]:
                phase = memo[forwards][at]
                break
            trail.append(at)
            at = mod.chain_step(at, forwards)
            if at is not None and phases[at][0]:
                phase = phases[at]
                break
        memo[forwards].update(dict.fromkeys(trail, phase))
        if phase:
            return phase
    return None


def _by_dataflow(mod: _Module, phases: Dict[str, Phase], sources: Dict[str, str]) -> None:
    """Gives the instructions metadata left without a phase the one the
    module's dataflow shows, in place.  Each rule reads the phases as they
    stood before it began, so the order of the text decides nothing."""
    fused = set(mod.fused_of.values())      # what a fusion holds runs as the fusion
    left = [n for n in mod.own if not phases[n][0] and mod.kind[n] not in _NO_WORK
            and mod.computation_of[n] not in fused]
    # a piece of a split operation is made from its siblings
    found, memo = {}, ({}, {})
    for name in left:
        if mod.writes_in_place(name):
            found[name] = (_nearest_sibling(mod, phases, name, memo)
                           or mod.first_operand(name, phases))
    for name, phase in found.items():
        if phase:
            phases[name], sources[name] = phase, SIBLING
    # a move is made for its reader; a kernel the compiler named itself was
    # written under the scope of what it reads
    found = {}
    for name in left:
        kind = mod.kind[name]
        named = kind == "custom-call" and mod.own[name] is not None
        if phases[name][0] or not (named or kind in _MOVES or kind.endswith(_ASYNC_MOVES)):
            continue
        walks = ((mod.first_reader, READER), (mod.first_operand, OPERAND))
        for walk, source in (reversed(walks) if named else walks):
            phase = walk(name, phases)
            if phase:
                found[name] = (phase, source)
                break
    for name, (phase, source) in found.items():
        phases[name], sources[name] = phase, source
    # what runs inside an instruction that has a phase now runs under it
    # (callers stand after their callees in the text)
    for name in reversed(mod.own):
        at = mod.caller_of.get(mod.computation_of[name])
        if not phases[name][0] and at in phases and phases[at][0]:
            phases[name], sources[name] = phases[at], CONTAINER


def _fusion_mix(mod: _Module) -> Dict[str, Dict[str, int]]:
    mix = {}
    for name, computation in mod.fused_of.items():
        counts: Dict[str, int] = {}
        for inside in mod.members.get(computation, ()):
            path, backward = mod.own[inside] or _UNSCOPED
            if path:
                key = path[0] + (".bwd" if backward else "")
                counts[key] = counts.get(key, 0) + 1
        if len(counts) > 1:
            mix[name] = counts
    return mix


@functools.lru_cache(maxsize=1)
def _resolved(hlo_text: str):
    """(phases, sources, fusion mix) of one text.  The last text's are kept
    (and the text with them), so the three public functions cost one parse."""
    mod = _Module(hlo_text)
    phases: Dict[str, Phase] = {}
    sources: Dict[str, str] = {}
    for name in mod.own:
        phases[name], sources[name] = mod.by_metadata(name)
    _by_dataflow(mod, phases, sources)
    return phases, sources, _fusion_mix(mod)


def instruction_phases(hlo_text: str) -> Dict[str, Phase]:
    """``{instruction name: (phase path, is_backward)}`` over every
    instruction of an optimized HLO module's text.  Metadata first: the
    instruction's own ``op_name``; a fusion without one that of the
    instructions it fused (the one nearest its root that has an
    ``op_name``); anything else that of the innermost ``while`` /
    ``conditional`` / ``call`` / fusion instruction whose computation holds
    it.  What that leaves without a phase is read from the module's
    dataflow, inside the instruction's own computation, by what it is:

    * an in-place writer or join (``dynamic-update-slice``, ``concatenate``,
      ``pad``, or a fusion rooted in one with no ``op_name`` inside) is a
      piece of an operation the compiler split, and takes the phase of the
      nearest writer that has one along the chain over its buffer (operand 0
      backwards, then the readers that write the result in place), failing
      that of the first operand that has one;
    * a move (``copy``, ``transpose``, the ``-done`` of an asynchronous pair)
      is made for its reader: the first instruction in program order that
      reads it and has a phase, failing that the first operand that has one;
      a ``custom-call`` the compiler named itself
      (``op_name="ragged-dot-none"``) the other way round.  A ``-start``
      (no time of its own; the profiler names the whole asynchronous span by
      it) and a ``custom-call`` without ``op_name`` (the compiler's join of
      staged slices, counted as a kernel by whoever counts custom-calls
      under a scope) are looked through and keep ``()``.

    The walks step through instructions that have no phase, never leave the
    computation and end on a cycle.  What no rule explains keeps the path
    ``()``, which readers report as ``unscoped``."""
    return dict(_resolved(hlo_text)[0])


def instruction_phase_sources(hlo_text: str) -> Dict[str, str]:
    """``{instruction name: where its phase came from}``: ``own`` (its
    ``op_name``), ``fused`` (a fused instruction's), ``container`` (the
    enclosing instruction's), ``sibling`` (a split operation's other pieces),
    ``reader`` / ``operand`` (a move's reader or operand), ``none``."""
    return dict(_resolved(hlo_text)[1])


def fusion_phase_mix(hlo_text: str) -> Dict[str, Dict[str, int]]:
    """``{fusion: {top-level phase: fused instructions}}`` for the fusions
    whose fused instructions carry more than one top-level phase (``model``
    and ``model.bwd`` are two; two modules under ``model`` are one).  The
    fusion's own phase is its root's: the list says whose time it could as
    well be."""
    return {name: dict(counts) for name, counts in _resolved(hlo_text)[2].items()}
