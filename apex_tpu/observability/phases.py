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

Stdlib only: readers and ``/profilez`` consumers import it without jax.
"""

from __future__ import annotations

import re
from typing import Dict, Optional, Tuple

__all__ = ["PHASES", "phase_of_op_name", "instruction_phases"]

# scope -> where it is opened
PHASES = (
    "amp.scale_loss",      # amp/handle.py: loss * scale, and the divide back
    "amp.pack",            # AmpOptimizer.step: layout.pack(scaled_grads)
    "amp.unscale",         # scaler.unscale as called from step
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
    # models/laguna.py, attention with ``qk_norm``
    "attn.qk_norm",        # RMSNorm over each head of q and of k, before RoPE
)
MODEL = "model"

_VOCAB = frozenset(PHASES)
# op_name components jax itself puts between a module scope and the primitive
_JAX_STRUCTURE = frozenset((
    "cond", "while", "scan", "checkpoint", "remat", "pallas_call",
    "shard_map", "closed_call", "core_call", "custom_vjp_call",
    "custom_jvp_call", "custom_vjp_call_jaxpr", "custom_lin",
    "rematted_computation"))
_PLAIN = re.compile(r"[A-Za-z0-9_]+$")
_WRAPPED = re.compile(r"(?:[\w.\-]+\()*([^()]*)\)*$")

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s*\(.*\)\s*->.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([^\s=]+)\s+=\s")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLEE = re.compile(r"(?:to_apply|body|condition|true_computation|"
                     r"false_computation)=%?([^\s,}]+)")
_FUSED = re.compile(r"calls=%?([^\s,}]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_CUSTOM_CALL = re.compile(r"\scustom-call\(([^)]*)\)")
_OPERAND = re.compile(r"%([^\s,()]+)")

Phase = Tuple[Tuple[str, ...], bool]


def phase_of_op_name(op_name: str) -> Phase:
    """``(phase path, is_backward)`` of one ``op_name``: the vocabulary's
    scopes found in it, outermost first, with the module path that follows
    ``model`` kept whole as one element (``("model",
    "BertForPretraining/bert/3/attention/qkv")``); backward when a scope
    sits inside ``transpose(``.  ``((), False)`` when no scope is found."""
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
                modules = []
                # the last component is the primitive, never a module
                while i + 1 < len(parts) - 1:
                    nxt = parts[i + 1]
                    if not modules and (nxt in _JAX_STRUCTURE or _WRAPPED
                                        .match(nxt).group(1) == MODEL):
                        # a rematerialized block's backward: transpose(
                        # jvp(model))/jvp(model)/checkpoint/<modules>
                        i += 1
                        continue
                    if not (_PLAIN.match(nxt) and nxt not in _VOCAB
                            and nxt not in _JAX_STRUCTURE
                            and not nxt.startswith("branch_")):
                        break
                    i += 1
                    modules.append(nxt)
                if modules:
                    path.append("/".join(modules))
        i += 1
    backward = any("transpose(" in p for p in parts[:last + 1])
    return tuple(path), backward


def instruction_phases(hlo_text: str) -> Dict[str, Phase]:
    """``{instruction name: (phase path, is_backward)}`` over every
    instruction of an optimized HLO module's text.  An instruction without
    ``op_name`` takes the phase of what the compiler made it from or for: a
    fusion that of the instructions it fused (the one nearest its root
    that has an ``op_name``), anything else that of the innermost
    ``while`` / ``conditional`` / ``call`` / fusion instruction whose
    computation holds it; failing both its path is ``()``, which readers
    report as ``unscoped``.  A ``custom-call`` the compiler named itself
    (``op_name="ragged-dot-none"``: its own grouped-product kernel for
    ``lax.ragged_dot``, and the kernel that prepares its tiles) takes the
    phase of the first of its operands that has one, or else of the first
    instruction that reads it."""
    own: Dict[str, Optional[Phase]] = {}
    computation_of: Dict[str, str] = {}
    caller_of: Dict[str, str] = {}          # computation -> calling instruction
    fused_of: Dict[str, str] = {}           # fusion instruction -> its computation
    members: Dict[str, list] = {}           # computation -> its instructions
    named_by_compiler: Dict[str, list] = {}  # custom-call -> its operands, then its readers
    current = None
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c:
                current = c.group(1)
            continue
        name = m.group(1)
        op = _OP_NAME.search(line)
        own[name] = phase_of_op_name(op.group(1)) if op else None
        computation_of[name] = current
        members.setdefault(current, []).append(name)
        call = _CUSTOM_CALL.search(line)
        if call and own[name] == ((), False):
            named_by_compiler[name] = _OPERAND.findall(call.group(1))
        for read in _OPERAND.findall(line[m.end():]):
            if read in named_by_compiler and read != name:
                named_by_compiler[read].append(name)
        callees = _CALLEE.findall(line)
        fused = _FUSED.search(line)
        if fused:
            fused_of[name] = fused.group(1)
            callees.append(fused.group(1))
        for group in _BRANCHES.findall(line):
            callees += [b.strip().lstrip("%") for b in group.split(",")]
        for callee in callees:
            caller_of.setdefault(callee, name)

    out: Dict[str, Phase] = {}

    def resolve(name: str) -> Phase:
        if name in out:
            return out[name]
        phase, seen, at = own[name], {name}, name
        if name in named_by_compiler:
            out[name] = phase               # (a cycle through such calls ends here)
            phase = next((p for p in map(resolve, (o for o in named_by_compiler[name]
                                                   if o in own)) if p[0]), phase)
        if phase is None:
            phase = next((own[i] for i in reversed(members.get(fused_of.get(name), ()))
                          if own[i] is not None), None)
        while phase is None:
            at = caller_of.get(computation_of.get(at))
            if at is None or at in seen or at not in own:
                phase = ((), False)
                break
            seen.add(at)
            phase = own[at]
        out[name] = phase
        return phase

    for name in own:
        resolve(name)
    return out
