"""Continuous-batching serving engine (vLLM-lite, fixed slots).

The reference toolkit predates LLM serving; generate_cached covers the
static-batch case, and this engine covers the real serving shape:
requests ARRIVE and FINISH at different times, and the decode step
always runs the full slot batch so the MXU stays busy while individual
sequences come and go.

Design (deliberately simple — correctness over paging):

- ``slots`` fixed sequences of length ``buf_len``; per-slot KV cache
  rows inside the usual (B, Hkv, S, D) buffers;
- ``add_request`` claims a free slot, seeds ITS cache row with a
  chunked prefill of the prompt (one scatter per layer), no impact on
  other slots;
- ``step()`` is ONE jitted dispatch: a DECODE WINDOW of ``window``
  in-graph decode ticks (``lax.scan`` over ``decode_chunk(L=1)`` at
  per-slot positions + greedy head, models/llama.py decode_chunk
  contract), emitting a ``[slots, window]`` token buffer plus validity
  masks that the host unpacks ONCE per window — the per-token
  host-sync tax becomes a per-window tax.  Inactive slots decode
  garbage that the masks drop; a slot that hits its EOS or token
  limit mid-window FREEZES in-graph (ids/cur_len/cache/RNG stream
  stop advancing) so exactness survives any window size; arrivals
  are admitted at window boundaries;
- every jitted cache mutator donates its KV buffers
  (``donate_argnums``): the multi-GB cache is updated in place
  instead of XLA keeping a second copy alive across every tick;
- a request finishes on ``eos_token_id`` or its ``max_new_tokens``;
  the slot frees immediately and can be reclaimed next ``add_request``;
- optional PREFIX SHARING (``prefix_pool``): registered prompt
  prefixes are prefilled once into pool rows; matching requests admit
  by a static KV row-copy + suffix-only chunked prefill (see
  ``Engine.__init__``) — the static-shape answer to vLLM's prefix
  cache.

Exactness (greedy and speculative-greedy paths): a request's output is
token-for-token what ``generate_cached`` would produce for it alone —
regardless of what other requests share the batch (pinned in
tests/test_serving.py with staggered arrivals).  Sampled mode
(``temperature > 0``) draws each request from its own key stream,
advanced once per its own decode step — co-tenants and arrival timing
never perturb it.  With an explicit ``submit(..., seed=N)`` the stream
is request-intrinsic (fully batch-independent, pinned in tests); the
default stream keys off the request id, i.e. it is deterministic given
the engine's SUBMISSION ORDER.  The two namespaces are
domain-separated, so an explicit seed never collides with an auto id.

Works with any model exposing ``prefill_cache`` / ``decode_chunk`` /
``init_cache`` and a greedy head (GPT, Llama and its Mistral / Qwen2 /
Gemma / NeoX configs).  MoE models must be served DROPLESS
(``capacity_factor >= n_experts``, e.g. a ``mixtral_from_hf`` config):
capacity-bounded routing would make one request's tokens depend on
which other requests share the batch, and the constructor rejects it.

Encoder-decoder models (T5) get their own :class:`Seq2SeqEngine`: the
per-slot residents are the request's precomputed cross-attention K/V
and a decoder self-attention cache instead of one decoder KV cache.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .models.speculative import _head_logits
from .observability import MetricsRegistry
# every engine jit routes through the compilation ledger: the entry
# label + abstract-signature record is what the zero-retrace
# steady-state contract (tests/test_serving.py) and the fleet's
# survivors-recompile-nothing pin measure deltas over.  The wrapper's
# bookkeeping is host-side python — the traced graphs are unchanged,
# so the donation/host-transfer audits hold as before.
from .observability.compilation import instrumented_jit
# ambient-gated spans: these record ONLY when a distributed-trace
# context is active on the calling thread (a fleet dispatching a traced
# request), so a standalone engine pays one contextvar read per call
# and its process recorder never grows — and nothing here touches the
# jitted graphs, so the zero-host-transfer audit is unaffected.
from .observability.tracing import maybe_event, maybe_span

__all__ = ["Engine", "PagedEngine", "Seq2SeqEngine",
           "DONATION_BLOCKLIST", "STEP_K_ARG_NAMES",
           "PREFILL_SLOT_ARG_NAMES", "SEQ2SEQ_STEP_K_ARG_NAMES",
           "PAGED_STEP_K_ARG_NAMES", "PAGED_ADMIT_ARG_NAMES"]

# Argument names the engine jits must NEVER donate: per-slot length
# vectors.  Donating `_sstep`'s cur_len made executables RELOADED from
# the persistent XLA:CPU compile cache decode garbage (fresh compiles
# fine — single runs pass, the next warm run hangs; seen under jax
# 0.4.37, PR 2; tests/ci/double_run.py is the runtime gate).  apex_tpu.analysis's donation rule enforces this
# blocklist over every registered serving entry point, so the gotcha
# stays pinned even if the inline comments rot.  kv_len (positions
# prefilled so far) and n_blk (blocks held) are the paged engine's
# members of the same per-slot-length-vector class.
DONATION_BLOCKLIST = ("cur_len", "n_new", "kv_len", "n_blk")

# Positional parameter names of the jitted hot mutators, in signature
# order — the analysis donation rule maps `Lowered.args_info` donation
# flags back through these to name what is (and is not) aliased.
STEP_K_ARG_NAMES = ("ids", "cur_len", "cache", "keys", "temps",
                    "limit", "eos")
PREFILL_SLOT_ARG_NAMES = ("ids", "cache", "d_cache", "slot", "row")
SEQ2SEQ_STEP_K_ARG_NAMES = ("state", "out", "n_new", "limit", "eos")
PAGED_STEP_K_ARG_NAMES = ("ids", "cur_len", "kv_len", "pool", "keys",
                          "temps", "limit", "eos", "tables", "n_blk",
                          "free_stack", "free_top", "pending")
PAGED_ADMIT_ARG_NAMES = ("ids", "cur_len", "kv_len", "limit", "eos",
                         "keys", "temps", "tables", "n_blk",
                         "free_stack", "free_top", "slot", "row",
                         "plen", "lim", "eos_id", "key", "temp",
                         "n_need")

# generated tokens/sec per request spans toy CPU engines (~1/s) to
# hardware batch decode (~10k/s)
_TPS_BUCKETS = (1.0, 2.0, 5.0, 10.0, 20.0, 50.0, 100.0, 200.0, 500.0,
                1000.0, 2000.0, 5000.0, 10000.0, 20000.0)


def _tree_nbytes(tree) -> int:
    """Device bytes across a pytree's array leaves — the one leaf-
    accounting rule `kv_cache_bytes` and both engines' fragmentation
    ledgers share (so they can never drift)."""
    return int(sum(leaf.nbytes
                   for leaf in jax.tree_util.tree_leaves(tree)
                   if hasattr(leaf, "nbytes")))


class _Request:
    def __init__(self, rid, slot, prompt_len, max_new, eos):
        self.rid = rid
        self.slot = slot
        self.prompt_len = prompt_len
        self.max_new = max_new
        self.eos = eos
        self.generated: List[int] = []
        self.done = False
        # telemetry timestamps (engine clock): queue entry, slot
        # admission, first emitted token, finish
        self.t_submit: Optional[float] = None
        self.t_admit: Optional[float] = None
        self.t_first: Optional[float] = None
        self.t_finish: Optional[float] = None


class _SlotScheduler:
    """Shared request-lifecycle machinery for both engines: slot
    bookkeeping, the FIFO submit queue, and result harvesting.
    Subclasses provide ``_admit(rid, prompt, max_new, eos)`` (claim
    ``self._free.pop()`` and seed device state) and
    ``_check_prompt(prompt)`` (shape validation), plus their own
    ``step()``."""

    def _init_scheduler(self, slots: int,
                        metrics: Optional[MetricsRegistry] = None):
        self._free = list(range(slots))
        self._waiting: List[Any] = []
        self._by_slot: Dict[int, _Request] = {}
        self._finished: Dict[int, _Request] = {}
        self._next_rid = 0
        # -- telemetry: per-engine registry (pass one in to aggregate
        # several engines or to export alongside other process metrics)
        self._clock = time.perf_counter
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self._submit_ts: Dict[int, float] = {}
        # rid -> tenant tag (observability only: stamped on the
        # engine's queue/prefill spans so engine-internal hops inside
        # a fleet trace say whose request they served); dropped with
        # the request (finish/cancel/take_waiting)
        self._tenant_tags: Dict[int, str] = {}
        # engine-LOCAL totals for stats(): registry counters are shared
        # when several engines share a registry, and per-engine fields
        # (notably prefix_hit_rate's denominator) must not conflate
        # another engine's traffic
        self._n_admitted = 0
        self._n_tokens = 0
        self._n_steps = 0
        self._n_syncs = 0
        self._n_preempted = 0
        self._last_util = 0.0
        self.window = int(getattr(self, "window", 1))
        self._m_prefill = self.metrics.histogram(
            "engine_prefill_seconds",
            help="admission latency: prompt prefill + slot seed")
        self._m_decode = self.metrics.histogram(
            "engine_decode_step_seconds",
            help="per-TOKEN decode latency: one window's wall time "
                 "(incl. the host fetch) / tokens it emitted")
        self._m_queue_wait = self.metrics.histogram(
            "engine_queue_wait_seconds",
            help="submit-to-admission wait in the FIFO queue")
        self._m_ttft = self.metrics.histogram(
            "engine_ttft_seconds",
            help="submit to first emitted token, per request")
        self._m_tps = self.metrics.histogram(
            "engine_request_tokens_per_sec", buckets=_TPS_BUCKETS,
            help="generated tokens/sec per finished request")
        self._m_admitted = self.metrics.counter("engine_admitted_total")
        self._m_finished = self.metrics.counter("engine_finished_total")
        self._m_tokens = self.metrics.counter("engine_tokens_total")
        self._m_steps = self.metrics.counter(
            "engine_decode_steps_total",
            help="device decode dispatches (one per window, NOT per "
                 "token)")
        self._m_syncs = self.metrics.counter(
            "engine_host_syncs_total",
            help="device->host result fetches the decode loop paid "
                 "(one per window; 1/window per token when full)")
        self.metrics.gauge(
            "engine_window_size",
            help="in-graph decode ticks per host round trip").set(
            float(self.window))
        # the fragmentation gauges start honest: everything allocated,
        # nothing used (subclasses call _init_scheduler after their KV
        # buffers exist)
        self._set_kv_gauges()

    def _admit_timed(self, rid, *rest, refresh_kv=True):
        """All admissions (direct and queue-drained) route through here:
        times the prefill/seed, stamps the request's lifecycle
        timestamps, and feeds the admission histograms.
        ``refresh_kv=False`` lets a batch drain defer the fragmentation
        ledger rebuild to ONE refresh at its end instead of one full
        KV-tree scan per admitted request."""
        t0 = self._clock()
        # engine_rid, not rid: these spans land inside FLEET request
        # traces whose rid attrs are fleet ids — the replica-local id
        # is a different namespace and must not join against them
        tenant = self._tenant_tags.get(rid)
        with maybe_span("engine_prefill", engine_rid=rid,
                        **({"tenant": tenant} if tenant is not None
                           else {})):
            self._admit(rid, *rest)
        t1 = self._clock()
        self._m_prefill.observe(t1 - t0)
        self._m_admitted.inc()
        self._n_admitted += 1
        req = next((r for r in self._by_slot.values() if r.rid == rid),
                   None)
        if req is not None:
            req.t_submit = self._submit_ts.pop(rid, t0)
            req.t_admit = t1
            self._m_queue_wait.observe(max(t0 - req.t_submit, 0.0))
        if refresh_kv:
            self._set_kv_gauges()   # admission filled a slot's prefix

    def _record_step(self, t0: float, tokens: int = 1,
                     capacity: int = 0) -> float:
        """Per-dispatch bookkeeping after the device fetch; returns
        `now` so harvest loops stamp first-token times without
        re-reading the clock per request.  ``tokens`` is what the
        window emitted (the decode histogram observes wall time /
        tokens — per-TOKEN latency, not raw window time);
        ``capacity`` is ``live_slots * window``, the window's token
        budget, feeding the utilization gauge (speculative ticks can
        exceed 1.0 — that is the acceptance rate showing)."""
        now = self._clock()
        self._m_decode.observe((now - t0) / max(tokens, 1))
        self._m_steps.inc()
        self._n_steps += 1
        self._m_syncs.inc()
        self._n_syncs += 1
        if capacity > 0:
            self._last_util = tokens / capacity
            self.metrics.gauge(
                "engine_window_utilization",
                help="tokens emitted / (live slots * window size) of "
                     "the last dispatch").set(self._last_util)
        self.metrics.gauge("engine_live").set(len(self._by_slot))
        self.metrics.gauge("engine_queue_depth").set(len(self._waiting))
        self.metrics.gauge("engine_occupancy").set(
            len(self._by_slot) / self.slots)
        return now

    def _harvest(self, emitted, t0):
        """Shared post-dispatch harvest for both engines: per-token
        metrics, first-token stamps, EOS truncation (windowed paths
        already mask in-graph — this also covers the speculative path,
        whose accepted run can cross the EOS), finish + device-freeze
        of done slots, queue drain.  ``emitted`` maps every live slot
        to the tokens its request emitted this dispatch."""
        n_emitted = sum(len(t) for t in emitted.values())
        now = self._record_step(t0, tokens=n_emitted,
                                capacity=len(emitted) * self.window)
        out: Dict[int, Any] = {}
        for slot, req in list(self._by_slot.items()):
            toks = emitted[slot]
            if req.eos is not None and req.eos in toks:
                toks = toks[:toks.index(req.eos) + 1]
            req.generated.extend(toks)
            if toks:
                out[req.rid] = list(toks)
                if req.t_first is None:
                    req.t_first = now
                self._m_tokens.inc(len(toks))
                self._n_tokens += len(toks)
            hit_eos = req.eos is not None and req.eos in toks
            if hit_eos or self._out_of_budget(req):
                self._finish(slot, req)
                # stop the device from advancing the freed slot (also
                # what marks it inactive for the next window's scan)
                self._freeze_slot(slot)
        self._drain_queue()
        # after the window's growth/finishes and the re-admissions:
        # the per-window fragmentation sample the ISSUE's ledger asks
        # for (admissions inside _drain_queue already refreshed, but a
        # window with only finishes/growth would otherwise go stale)
        self._set_kv_gauges()
        return out

    def _check_request(self, prompt, max_new_tokens, seed,
                       temperature):
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got "
                             f"{max_new_tokens}")
        if seed is not None and not self._supports_seed:
            raise ValueError("per-request seed is only meaningful for "
                             "the sampled decoder-only Engine")
        if temperature is not None:
            if not self._supports_temperature:
                raise ValueError(
                    "per-request temperature needs an engine built "
                    "with temperature > 0 (the sampled tick); greedy "
                    "and speculative engines have no override point")
            if not (temperature >= 0):    # also rejects NaN
                raise ValueError(f"temperature must be >= 0, got "
                                 f"{temperature}")
        self._check_prompt(prompt)

    _supports_seed = False
    _supports_temperature = False
    # duck-typed capability flag: a fleet passes its request's tenant
    # tag through to replicas that advertise it (stub/proxy replicas
    # without the flag keep the pre-tenant dispatch signature)
    accepts_tenant = True
    # how this engine admits requests and holds KV: "fixed_slot" (one
    # contiguous buf_len row per slot, admission when a slot frees) or
    # "paged" (block-pool KV + iteration-boundary admission).  Exported
    # in ``stats()`` and ``/statusz``.
    admission_mode = "fixed_slot"

    def _can_admit_direct(self, prompt, max_new_tokens) -> bool:
        """Admission-control hook for :meth:`submit`: True when the
        engine can admit THIS request right now rather than queue it.
        The fixed-slot engines only need a free slot; the paged engine
        also needs block headroom."""
        return bool(self._free)

    def add_request(self, prompt: Sequence[int],
                    max_new_tokens: int,
                    eos_token_id: Optional[int] = None,
                    seed: Optional[int] = None,
                    temperature: Optional[float] = None,
                    tenant: Optional[str] = None) -> int:
        """Claim a slot, seed it, return the request id.  Raises if no
        slot is free (``submit`` queues instead).  ``seed`` names a
        request-intrinsic sampling stream and ``temperature`` overrides
        the engine default for THIS request (0.0 = greedy row) — both
        Engine-sampled-mode only; validated HERE so a bad request fails
        at submission, not mid-harvest in a later ``step()``.
        ``tenant`` is an opaque observability tag stamped on the
        request's engine-side spans (queue/prefill)."""
        if not self._free:
            raise RuntimeError("no free slot; harvest finished "
                               "requests, use submit(), or add "
                               "capacity")
        self._check_request(prompt, max_new_tokens, seed, temperature)
        rid = self._next_rid
        self._next_rid += 1
        if tenant is not None:
            self._tenant_tags[rid] = str(tenant)
        self._submit_ts.setdefault(rid, self._clock())
        self._admit_timed(rid, prompt, max_new_tokens, eos_token_id, seed,
                          temperature)
        return rid

    def submit(self, prompt: Sequence[int], max_new_tokens: int,
               eos_token_id: Optional[int] = None,
               seed: Optional[int] = None,
               temperature: Optional[float] = None,
               tenant: Optional[str] = None) -> int:
        """``add_request`` that QUEUES when the engine is full; queued
        requests are admitted automatically as slots free at the end
        of each ``step()`` (arrival order)."""
        self._check_request(prompt, max_new_tokens, seed, temperature)
        if not self._waiting and self._can_admit_direct(prompt,
                                                        max_new_tokens):
            return self.add_request(prompt, max_new_tokens,
                                    eos_token_id, seed, temperature,
                                    tenant=tenant)
        rid = self._next_rid
        self._next_rid += 1
        if tenant is not None:
            self._tenant_tags[rid] = str(tenant)
        self._submit_ts[rid] = self._clock()
        self._waiting.append((rid, list(prompt), max_new_tokens,
                              eos_token_id, seed, temperature))
        self._set_queue_gauge()
        maybe_event("engine_queue", engine_rid=rid,
                    queue_depth=len(self._waiting),
                    **({"tenant": str(tenant)} if tenant is not None
                       else {}))
        return rid

    def _set_queue_gauge(self):
        # the gauge must track every mutation of the waiting queue, not
        # only the end-of-step snapshot: the fleet layer sheds, drains
        # and re-enqueues between steps, and its tests read the gauge
        # against stats()["queue_depth"] after each such move
        self.metrics.gauge("engine_queue_depth").set(len(self._waiting))

    def _drain_queue(self):
        admitted = False
        while self._free and self._waiting:
            self._admit_timed(*self._waiting.pop(0), refresh_kv=False)
            admitted = True
        self._set_queue_gauge()
        if admitted:
            self._set_kv_gauges()   # one ledger rebuild per drain

    def take_waiting(self) -> List[tuple]:
        """Pop and return the whole waiting queue (FIFO order) as
        ``(rid, prompt, max_new_tokens, eos_token_id, seed,
        temperature)`` tuples — the drain/failover hook: a fleet
        re-enqueues these onto surviving replicas.  The popped rids are
        dead to THIS engine (its queue-depth gauge and stats drop
        them); the caller owns re-submission."""
        taken, self._waiting = self._waiting, []
        for rid, *_ in taken:
            self._submit_ts.pop(rid, None)
            self._tenant_tags.pop(rid, None)
        self._set_queue_gauge()
        return taken

    def free_slots(self) -> int:
        """Slots a new request could claim right now (admission-control
        surface for routers that must not grow ``_waiting``)."""
        return len(self._free)

    def queue_depth(self) -> int:
        """Waiting-queue length, without the histogram-summary cost of
        ``stats()`` — the fleet router reads this every dispatch."""
        return len(self._waiting)

    def is_finished(self, rid: int) -> bool:
        """True once ``result(rid)`` will return (harvest surface for a
        fleet polling many replicas)."""
        return rid in self._finished

    def cancel(self, rid: int) -> bool:
        """Abandon a request: a waiting request is dropped from the
        queue, a live one frees its slot and freezes on device (its
        partial tokens are discarded — it never enters ``result()``).
        Returns False for unknown/finished rids.  The fleet layer uses
        this to clear stale work off a replica being drained or
        recovered after a failover."""
        for i, item in enumerate(self._waiting):
            if item[0] == rid:
                del self._waiting[i]
                self._submit_ts.pop(rid, None)
                self._tenant_tags.pop(rid, None)
                self._set_queue_gauge()
                return True
        for slot, req in list(self._by_slot.items()):
            if req.rid == rid:
                self._tenant_tags.pop(rid, None)
                del self._by_slot[slot]
                self._free.append(slot)
                self._freeze_slot(slot)
                self.metrics.gauge("engine_live").set(len(self._by_slot))
                self.metrics.gauge("engine_occupancy").set(
                    len(self._by_slot) / self.slots)
                self._set_kv_gauges()   # the slot's KV row is waste now
                return True
        return False

    def preempt(self, rid: int) -> bool:
        """Evict a request to make room for a higher-priority one (the
        fleet QoS plane's eviction API).  Mechanically this is
        :meth:`cancel` — the slot frees, a paged engine returns the
        victim's KV blocks through the same eager host-side recycling
        path (``_freeze_slot``), so a warmed engine preempts with
        ZERO new traces — but the intent differs and is accounted
        separately: ``preempted`` in :meth:`stats` and the
        ``engine_preempted_total`` counter name evictions, not
        abandonments.  The caller owns re-queueing the victim from its
        prompt (exactness holds: greedy / explicitly-seeded decodes
        are request-intrinsic).  Returns False for unknown/finished
        rids, like ``cancel``."""
        ok = self.cancel(rid)
        if ok:
            self._n_preempted += 1
            self.metrics.counter(
                "engine_preempted_total",
                help="requests evicted mid-decode by the fleet QoS "
                     "plane (slot freed, KV blocks recycled)").inc()
        return ok

    def _finish(self, slot, req):
        req.done = True
        req.t_finish = self._clock()
        self._tenant_tags.pop(req.rid, None)
        del self._by_slot[slot]
        self._free.append(slot)
        self._finished[req.rid] = req
        self._m_finished.inc()
        if req.t_first is not None and req.t_submit is not None:
            self._m_ttft.observe(req.t_first - req.t_submit)
        if req.generated and req.t_admit is not None:
            dur = req.t_finish - req.t_admit
            if dur > 0:
                self._m_tps.observe(len(req.generated) / dur)

    def result(self, rid: int) -> List[int]:
        """Generated tokens (incl. EOS if hit) for a finished request."""
        return list(self._finished[rid].generated)

    def live(self) -> int:
        return len(self._by_slot)

    def compile_census(self) -> Dict[str, str]:
        """The expected-closure compile census: every compilation-
        ledger entry THIS engine's configuration will trace, mapped to
        the lifecycle stage that first traces it (``admission`` /
        ``decode`` trace during :meth:`warmup`; ``register_prefix`` /
        ``prefix_admission`` trace when the prefix pool is actually
        used).  The zero-retrace contract tests compare the ledger's
        observed entries against this — a closure compiling that the
        census does not name is a compile-plane surprise."""
        return {}

    def warmup(self):
        """Pre-compile the engine's admission + decode closures before
        traffic by running ONE throwaway request (1-token prompt, one
        window) end to end.  Every ``Engine`` instance re-jits its own
        closures, so a cold fleet pays N compiles on its first timed
        window unless each replica is warmed first — the PR 4 bench
        gotcha, fixed at the source here (``Fleet.warmup`` fans this
        out over its replicas).  Requires an idle engine; the warmup
        request is scrubbed from ``result()`` but does consume one
        request id and feeds the admission/decode histograms (a
        sampled engine's default rid-keyed streams shift by one —
        pass explicit seeds where exactness against an unwarmed twin
        matters).  Returns ``self``."""
        if self._by_slot or self._waiting:
            raise RuntimeError(
                "warmup() needs an idle engine (no live or queued "
                "requests); warm before traffic")
        rid = self.add_request([0], max_new_tokens=1)
        while not self.is_finished(rid):
            self.step()
        self._finished.pop(rid, None)
        return self

    def _kv_buffers(self):
        """Pytrees of device-resident KV state this engine owns —
        subclasses override; the base scheduler has none."""
        return []

    def kv_cache_bytes(self) -> int:
        """Device bytes held by this engine's KV cache buffers (slot
        caches, draft caches, prefix-pool rows; seq2seq slot state).
        The paged-KV refactor (ROADMAP item 1) is judged against this
        number — it is recomputed from the live buffers, so a layout
        change cannot silently stop being counted."""
        return sum(_tree_nbytes(buf) for buf in self._kv_buffers())

    # -- KV fragmentation ledger (PR 13) -------------------------------
    # ``kv_cache_bytes`` says what the engine ALLOCATED; the paged-KV
    # refactor is really judged on what it WASTES — capacity positions
    # reserved for a slot beyond what its request's cur_len occupies
    # (plus whole rows held by free slots and unregistered pool rows).
    # Everything here is computed from host-side mirrors (the request
    # records' prompt_len + generated, which track the device cur_len
    # exactly) and leaf .nbytes — zero device syncs, zero new prims in
    # any jitted graph.

    def _kv_usage(self):
        """(slot_entries, pool_entries) — subclass hook; each entry
        carries at least ``used_bytes`` / ``kv_waste_bytes`` ints."""
        return [], []

    def kv_fragmentation(self) -> Dict[str, Any]:
        """The full per-slot ledger: allocated / used / wasted bytes,
        the utilization fraction, and one entry per slot (and prefix
        pool row) naming what occupies it — the number ROADMAP item
        1's paged allocator must drive down, per slot so the dashboard
        can see WHERE the waste sits."""
        total = self.kv_cache_bytes()
        slots, pools = self._kv_usage()
        used = min(int(sum(e["used_bytes"] for e in slots)
                       + sum(e["used_bytes"] for e in pools)), total)
        return {"kv_cache_bytes": total,
                "kv_used_bytes": used,
                "kv_waste_bytes": total - used,
                "kv_utilization": (used / total if total else 0.0),
                "slots": slots, "pools": pools}

    def kv_waste_bytes(self) -> int:
        """Allocated-but-unused KV bytes right now (see
        :meth:`kv_fragmentation`)."""
        return self.kv_fragmentation()["kv_waste_bytes"]

    def kv_utilization(self) -> float:
        """Used / allocated KV bytes in [0, 1] (0.0 on an engine with
        no KV state)."""
        return self.kv_fragmentation()["kv_utilization"]

    def _set_kv_gauges(self) -> Dict[str, Any]:
        """Refresh the fragmentation gauges from one ledger snapshot;
        wired at the same mutation points as ``engine_queue_depth``
        (admission, window harvest, cancel), so gauge == stats()
        through submit/step/cancel/eos — the fleet tests pin queue
        depth that way and the serving tests pin these the same way."""
        frag = self.kv_fragmentation()
        self.metrics.gauge(
            "engine_kv_waste_bytes",
            help="allocated-but-unused KV bytes (slot capacity beyond "
                 "cur_len, free slots, empty pool rows) — ROADMAP "
                 "item 1's fragmentation needle").set(
            frag["kv_waste_bytes"])
        self.metrics.gauge(
            "engine_kv_utilization",
            help="used / allocated KV bytes of this engine's "
                 "buffers").set(frag["kv_utilization"])
        return frag

    def stats(self) -> Dict[str, Any]:
        """Scheduler + telemetry snapshot.  The four original counters
        (live/waiting/free/finished) keep their flat-int shape; the
        telemetry additions are occupancy ratios, monotonic totals, and
        latency-histogram summaries ({count, sum, mean, p50, p99} — the
        percentiles are fixed-bucket estimates).  ``queue_depth``
        mirrors ``waiting`` under the name the metrics registry uses.
        The scalar totals are engine-LOCAL; the histogram summaries come
        from ``self.metrics``, so with an explicitly shared registry
        they aggregate every engine sharing it.

        Memory fields (PR 8): ``kv_cache_bytes`` (this engine's KV
        buffers), ``device_live_bytes`` (process-wide
        ``jax.live_arrays`` census, also folded into the registry's
        ``device_live_bytes`` gauge), and HBM occupancy where the
        backend reports real memory stats (``hbm_bytes_in_use`` /
        ``hbm_bytes_limit`` / ``hbm_occupancy``; None on CPU-style
        backends — the live census is the portable signal there).

        Fragmentation fields (PR 13): ``kv_waste_bytes`` /
        ``kv_utilization`` from the same ledger snapshot the
        ``engine_kv_waste_bytes`` / ``engine_kv_utilization`` gauges
        are set from — gauge == stats() by construction (the
        queue-depth pinning discipline)."""
        from .observability import memory as obs_memory
        frag = self._set_kv_gauges()
        kv = frag["kv_cache_bytes"]
        self.metrics.gauge(
            "engine_kv_cache_bytes",
            help="device bytes held by this engine's KV buffers"
        ).set(kv)
        census = obs_memory.record_live_arrays(self.metrics)
        hw = census.get("memory_stats")
        # memory_stats() keys are backend-dependent — guard each one
        occupancy = (hw["bytes_in_use"] / hw["bytes_limit"]
                     if hw and hw.get("bytes_limit")
                     and hw.get("bytes_in_use") is not None else None)
        return {"live": len(self._by_slot),
                "admission_mode": self.admission_mode,
                "kv_cache_bytes": kv,
                "kv_waste_bytes": frag["kv_waste_bytes"],
                "kv_utilization": frag["kv_utilization"],
                "device_live_bytes": census["bytes"],
                "hbm_bytes_in_use": hw.get("bytes_in_use") if hw else None,
                "hbm_bytes_limit": hw.get("bytes_limit") if hw else None,
                "hbm_occupancy": occupancy,
                "waiting": len(self._waiting),
                "free": len(self._free),
                "finished": len(self._finished),
                "slots": self.slots,
                "occupancy": len(self._by_slot) / self.slots,
                "queue_depth": len(self._waiting),
                "admitted": self._n_admitted,
                "preempted": self._n_preempted,
                "tokens_generated": self._n_tokens,
                "decode_steps": self._n_steps,
                "window": self.window,
                "host_syncs": self._n_syncs,
                "window_utilization": self._last_util,
                "tokens_per_sync": (self._n_tokens / self._n_syncs
                                    if self._n_syncs else 0.0),
                "prefill_latency": self._m_prefill.summary(),
                "decode_step_latency": self._m_decode.summary(),
                "queue_wait": self._m_queue_wait.summary(),
                "ttft": self._m_ttft.summary(),
                "request_tokens_per_sec": self._m_tps.summary()}


class Engine(_SlotScheduler):
    def __init__(self, model, params, slots: int, buf_len: int,
                 cache_dtype=None, draft=None, draft_params=None,
                 gamma: int = 4, temperature: float = 0.0,
                 top_k=None, top_p=None, rng=None,
                 prefix_pool: int = 0, prefix_chunk: int = 32,
                 rolling: bool = False, window: int = 1,
                 metrics: Optional[MetricsRegistry] = None):
        """``draft``/``draft_params`` switch ``step()`` to SPECULATIVE
        decoding: one ``spec_iteration`` (models/speculative.py) per
        tick, so every live request advances 1..gamma+1 tokens per
        step while staying token-for-token equal to its solo greedy
        decode.  ``temperature > 0`` samples instead (plain path only;
        combine with a draft for speculative SAMPLING semantics at the
        generate_speculative level).

        ``prefix_pool > 0`` enables PREFIX SHARING (the TPU-native
        answer to vLLM's prefix cache, minus paging — XLA wants static
        shapes, so reuse is row-granular, not block-granular):
        ``register_prefix(tokens)`` prefills a dedicated pool row once;
        any later request whose prompt starts with a registered prefix
        admits by gathering that pool row's KV, running only the
        SUFFIX through ``decode_chunk`` in ``prefix_chunk``-wide
        chunks against the (1, ...) row cache, and scattering the row
        into its slot — skipping the full-buffer prefill forward
        entirely.  Causality makes the
        spliced KV bit-identical to a fresh prefill (positions < L
        never see the suffix), so the solo-decode exactness contract is
        unchanged (pinned in tests/test_serving.py).  The chunk fn
        compiles once; chunks that would run past ``buf_len`` slide
        back and idempotently recompute the overlap.

        ``rolling=True`` serves a sliding-window model (Mistral-class)
        with O(window) KV memory per slot instead of O(buf_len):
        position p lives in ring slot p % W.  Admission prefills the
        prompt into a temporary full-width single-row cache, then
        relayouts the last W positions into the ring (one gather —
        exact, because a sliding-window model's decode never attends
        past W back).  The decode tick is the same ``decode_chunk``
        (L=1 rolling is wired in the model layer).  Incompatible with
        ``draft`` (speculative verify needs L>1 chunks) and
        ``prefix_pool`` (the splice relayout is not wired).

        ``window=K`` runs K decode ticks IN-GRAPH per ``step()``
        (``lax.scan``): the host fetches a ``[slots, K]`` token buffer
        + validity masks once per window instead of one token per
        round trip, so the per-token host-sync tax drops to 1/K.
        EOS/token-limit masking happens in-graph — a finished slot
        freezes mid-window — so the token-for-token exactness
        contract (vs ``generate_cached`` and vs the K=1 engine) is
        unchanged; arrivals are admitted at window boundaries, which
        bounds added TTFT at one window of ticks.  Incompatible with
        ``draft`` (spec_iteration already amortizes the sync over up
        to gamma+1 tokens; composing the two is not wired)."""
        self.model = model
        self.params = params
        self.slots = slots
        self.buf_len = buf_len
        self.draft = draft
        self.draft_params = draft_params
        self.gamma = gamma
        self.temperature = temperature
        self.window = int(window)
        if self.window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        if self.window > 1 and draft is not None:
            raise NotImplementedError(
                "windowed decode + speculative is not wired "
                "(spec_iteration already amortizes the host sync over "
                "up to gamma+1 tokens per tick); use window=1 with a "
                "draft")
        if temperature > 0.0 and draft is not None:
            raise NotImplementedError(
                "sampled speculative engine ticks are not wired; use "
                "greedy speculation or the plain sampled path")
        self._key = (rng if rng is not None
                     else jax.random.PRNGKey(0))
        # capacity-bounded MoE routing would make a request's tokens
        # depend on what else shares the batch, breaking the
        # batch-independence contract — require dropless experts
        from .parallel.expert_parallel import ExpertParallelMLP
        for mod in model.modules():
            if (isinstance(mod, ExpertParallelMLP)
                    and mod.capacity_factor < mod.n_experts):
                raise ValueError(
                    f"MoE layer with capacity_factor="
                    f"{mod.capacity_factor} < n_experts="
                    f"{mod.n_experts} can drop tokens depending on "
                    f"batch contents; serve dropless "
                    f"(capacity_factor >= n_experts) to keep requests "
                    f"batch-independent")
        if cache_dtype is None:
            # follow generate_cached's default: the table/param dtype
            cache_dtype = (model._table(params).dtype
                           if hasattr(model, "_table")
                           else params["wte"]["weight"].dtype)
        self.rolling = rolling
        if rolling:
            if draft is not None:
                raise NotImplementedError(
                    "rolling + speculative is not wired (verify needs "
                    "L>1 chunks over the ring)")
            if prefix_pool:
                raise NotImplementedError(
                    "rolling + prefix_pool is not wired")
            self._window = getattr(model.cfg, "sliding_window", None)
            if not self._window:
                raise ValueError("rolling=True requires a model with "
                                 "sliding_window set")
            if cache_dtype == jnp.int8:
                # admission prefills with fp attention reads, but the
                # solo rolling decode (step prefill) reads dequantized
                # int8 for layers >= 1 — the caches differ numerically
                # and the token-for-token contract would quietly break
                raise NotImplementedError(
                    "rolling + int8 cache is not wired (admission "
                    "parity with the solo step-prefill path)")
        self.ids = jnp.zeros((slots, buf_len), jnp.int32)
        self.cur_len = jnp.zeros((slots,), jnp.int32)
        self.limit = jnp.zeros((slots,), jnp.int32)   # per-slot final
        # per-slot EOS id for the in-graph window masking; -1 = none.
        # limit doubles as the liveness source: _finish zeroes it, so
        # cur_len < limit is exactly "this slot is serving a request"
        self._eos = jnp.full((slots,), -1, jnp.int32)
        self.cache = (model.init_cache(slots, dtype=cache_dtype,
                                       rolling=True) if rolling
                      else model.init_cache(slots, dtype=cache_dtype))
        self.d_cache = (draft.init_cache(slots, dtype=cache_dtype)
                        if draft is not None else None)
        self._init_scheduler(slots, metrics)

        def _seed(m, ps, cache, slot, row):
            row_cache = m.prefill_cache(ps, row[None, :],
                                        jax.tree_util.tree_map(
                lambda b: jnp.zeros((1,) + b.shape[1:], b.dtype), cache))
            return jax.tree_util.tree_map(
                lambda b, r: lax.dynamic_update_index_in_dim(
                    b, r[0].astype(b.dtype), slot, axis=0),
                cache, row_cache)

        def _prefill_slot(ids, cache, d_cache, slot, row):
            """Seed one slot: prefill the row alone, scatter its cache
            row into the batch cache(s)."""
            cache = _seed(model, params, cache, slot, row)
            if draft is not None:
                d_cache = _seed(draft, draft_params, d_cache, slot, row)
            ids = lax.dynamic_update_index_in_dim(ids, row, slot, axis=0)
            return ids, cache, d_cache

        # donate_argnums on every cache mutator: the KV buffers are
        # scattered/updated in place instead of XLA holding the old
        # multi-GB cache alive next to the new one per dispatch
        self._prefill_slot = instrumented_jit(
            _prefill_slot, "engine._prefill_slot",
            arg_names=PREFILL_SLOT_ARG_NAMES, donate_argnums=(0, 1, 2))

        if rolling:
            W = self._window

            def _prefill_slot_rolling(ids, cache, slot, row, plen):
                """Full-width single-row prefill, then relayout the
                last W positions into the ring (slot j <- the largest
                position p < plen with p % W == j; unwritten slots stay
                zero and the ring validity mask never selects them)."""
                full = model.prefill_cache(
                    params, row[None, :],
                    model.init_cache(1, dtype=cache_dtype))
                j = jnp.arange(W)
                p_j = plen - 1 - ((plen - 1 - j) % W)
                gather = jnp.maximum(p_j, 0)    # p_j < plen <= width

                def relayout(b, fb):
                    ring = jnp.take(fb[0], gather, axis=1)  # width ax 2
                    ring = jnp.where((p_j >= 0)[None, :, None],
                                     ring, 0)
                    return lax.dynamic_update_index_in_dim(
                        b, ring.astype(b.dtype), slot, axis=0)

                cache = jax.tree_util.tree_map(relayout, cache, full)
                ids = lax.dynamic_update_index_in_dim(ids, row, slot,
                                                      axis=0)
                return ids, cache

            self._prefill_slot_rolling = instrumented_jit(
                _prefill_slot_rolling, "engine._prefill_slot_rolling",
                arg_names=("ids", "cache", "slot", "row", "plen"),
                donate_argnums=(0, 1))

        # -- prefix-sharing pool ------------------------------------------
        if prefix_chunk < 1:
            raise ValueError(f"prefix_chunk must be >= 1, got "
                             f"{prefix_chunk}")
        self.prefix_pool = prefix_pool
        self.prefix_chunk = min(prefix_chunk, buf_len)
        self.prefix_hits = 0
        self._prefixes: List[tuple] = []
        if prefix_pool > 0:
            self._pool_cache = model.init_cache(prefix_pool,
                                                dtype=cache_dtype)
            self._pool_d_cache = (draft.init_cache(prefix_pool,
                                                   dtype=cache_dtype)
                                  if draft is not None else None)

            def _seed_pool(pool_cache, d_pool, idx, row):
                pool_cache = _seed(model, params, pool_cache, idx, row)
                if draft is not None:
                    d_pool = _seed(draft, draft_params, d_pool, idx,
                                   row)
                return pool_cache, d_pool

            self._seed_pool = instrumented_jit(
                _seed_pool, "engine._seed_pool",
                arg_names=("pool_cache", "d_pool", "idx", "row"),
                donate_argnums=(0, 1))

            # splice = one row gather from the pool, K suffix chunks on
            # the (1, ...) ROW cache (not the whole multi-slot tree —
            # no full-cache round trip per chunk), one scatter into the
            # slot.  Shared by target and draft caches.
            def _take_row(cache, idx):
                return jax.tree_util.tree_map(
                    lambda b: lax.dynamic_index_in_dim(
                        b, idx, 0, keepdims=True), cache)

            def _put_row(cache, rc, slot):
                return jax.tree_util.tree_map(
                    lambda b, r: lax.dynamic_update_index_in_dim(
                        b, r[0].astype(b.dtype), slot, axis=0),
                    cache, rc)

            # _take_row must NOT donate: the pool rows are the shared
            # prefix capital, reused by every later matching admission
            self._take_row = instrumented_jit(
                _take_row, "engine._take_row",
                arg_names=("cache", "idx"))
            self._put_row = instrumented_jit(
                _put_row, "engine._put_row",
                arg_names=("cache", "rc", "slot"), donate_argnums=(0,))
            self._chunk_row = {
                "cache": instrumented_jit(
                    lambda rc, t, o: model.decode_chunk(
                        params, t, jnp.full((1,), o, jnp.int32),
                        rc)[1],
                    "engine._chunk_row",
                    arg_names=("rc", "toks", "off"))}
            if draft is not None:
                self._chunk_row["d_cache"] = instrumented_jit(
                    lambda rc, t, o: draft.decode_chunk(
                        draft_params, t, jnp.full((1,), o, jnp.int32),
                        rc)[1],
                    "engine._chunk_row_draft",
                    arg_names=("rc", "toks", "off"))

        if draft is not None:
            from .models.speculative import spec_iteration

            def _sstep(ids, cur_len, limit, t_cache, d_cache):
                ids2, new_len, t_cache, d_cache, _ = spec_iteration(
                    model, params, draft, draft_params, ids, cur_len,
                    limit, ids, t_cache, d_cache, gamma)
                return ids2, new_len, t_cache, d_cache

            # NOT cur_len (argnum 1): donating it corrupts the
            # executable when reloaded from the persistent XLA:CPU
            # compilation cache (seen under jax 0.4.37 — fresh compiles
            # are fine, cache loads decode garbage; pinned by running
            # the serving suite twice against one cache dir).  The
            # multi-GB wins are the two cache trees; ids rides along.
            self._sstep = instrumented_jit(
                _sstep, "engine._sstep",
                arg_names=("ids", "cur_len", "limit", "t_cache",
                           "d_cache"),
                donate_argnums=(0, 3, 4))

        K = self.window

        def _step_k(ids, cur_len, cache, keys, temps, limit, eos):
            """K decode ticks in-graph (``lax.scan``) — ONE host round
            trip per window.  The carry holds a per-slot active mask:
            a slot that emits its EOS or reaches its token limit
            freezes for the rest of the window (ids/cur_len/cache/RNG
            stream stop advancing), so every request's tokens are
            exactly its solo decode regardless of K.  Emits the
            ``[slots, K]`` token buffer + validity mask the host
            unpacks once."""

            def tick(carry, _):
                ids, cur_len, cache, keys, alive = carry
                pos = jnp.maximum(cur_len - 1, 0)
                tok_in = jnp.take_along_axis(
                    ids, jnp.clip(pos, 0, buf_len - 1)[:, None], axis=1)
                # frozen/garbage slots recompute the KV their position
                # already holds (same token, same pos -> same values):
                # the write is idempotent, so the cache needs no mask
                h, cache = model.decode_chunk(params, tok_in, pos,
                                              cache)
                logits = _head_logits(model, params, h)[:, 0]
                if temperature > 0.0:
                    from .models import sampling as smp
                    # PER-SLOT key streams: each request draws from its
                    # own fold_in(base, seed) chain, advanced once per
                    # its OWN decode step (frozen slots hold their
                    # key), so its tokens depend only on its seed and
                    # step count — never on co-tenants, arrival timing,
                    # or the window size (batch-independent sampling)
                    split = jax.vmap(
                        lambda k: jax.random.split(k, 2))(keys)
                    new_keys, subs = split[:, 0], split[:, 1]
                    # per-request temperature: rows pre-scale their
                    # logits (sample_token at T=1 then filters — same
                    # semantics as a static temperature); a per-request
                    # T=0 row falls back to argmax via the where
                    safe_t = jnp.where(temps > 0, temps, 1.0)
                    scaled = (logits.astype(jnp.float32)
                              / safe_t[:, None])
                    sampled = jax.vmap(
                        lambda k, l: smp.sample_token(
                            k, l, 1.0, top_k=top_k,
                            top_p=top_p))(subs, scaled).astype(jnp.int32)
                    greedy = jnp.argmax(logits,
                                        axis=-1).astype(jnp.int32)
                    nxt = jnp.where(temps > 0, sampled, greedy)
                    keys = jnp.where(alive[:, None], new_keys, keys)
                else:
                    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                can = alive & (cur_len < buf_len)
                ids = jax.vmap(
                    lambda row, p, t, c: row.at[p].set(
                        jnp.where(c, t, row[p])))(
                    ids, jnp.minimum(cur_len, buf_len - 1), nxt, can)
                new_len = jnp.where(can, cur_len + 1, cur_len)
                emitted = alive
                hit_eos = (eos >= 0) & (nxt == eos)
                alive = alive & ~hit_eos & (new_len < limit)
                return ((ids, new_len, cache, keys, alive),
                        (nxt, emitted))

            alive0 = cur_len < limit
            (ids, cur_len, cache, keys, _), (toks, valid) = lax.scan(
                tick, (ids, cur_len, cache, keys, alive0), None,
                length=K)
            return ids, cur_len, cache, keys, toks.T, valid.T

        # donate ids + the KV cache + the key table, NOT cur_len: the
        # per-slot length vector is the argnum class whose donation
        # corrupts executables reloaded from the persistent XLA:CPU
        # compilation cache (see _sstep below), and donating a
        # (slots,)-int32 buys nothing anyway
        self._step_k = instrumented_jit(
            _step_k, "engine._step_k", arg_names=STEP_K_ARG_NAMES,
            donate_argnums=(0, 2, 3))
        self._slot_keys = jax.vmap(
            lambda i: jax.random.fold_in(self._key, i))(
            jnp.arange(slots))
        self._slot_temp = jnp.full((slots,), float(temperature),
                                   jnp.float32)
        # the prefix-pool/draft allocations above postdate
        # _init_scheduler's first ledger snapshot — refresh so the
        # gauges cover the full allocation from birth
        self._set_kv_gauges()

    # -- request lifecycle -------------------------------------------------
    def register_prefix(self, tokens: Sequence[int]) -> int:
        """Prefill ``tokens`` into a prefix-pool row once; later
        prompts starting with them admit via KV splice + suffix-only
        prefill.  Returns the pool index.  Requires ``prefix_pool``
        capacity at construction."""
        if self.prefix_pool == 0:
            raise RuntimeError("Engine built with prefix_pool=0")
        if len(self._prefixes) >= self.prefix_pool:
            raise RuntimeError(f"prefix pool full "
                               f"({self.prefix_pool} rows)")
        self._check_prompt(tokens)
        idx = len(self._prefixes)
        row = np.zeros((self.buf_len,), np.int32)
        row[:len(tokens)] = tokens
        self._pool_cache, self._pool_d_cache = self._seed_pool(
            self._pool_cache, self._pool_d_cache, idx,
            jnp.asarray(row))
        self._prefixes.append(tuple(int(t) for t in tokens))
        self._set_kv_gauges()           # the pool row is occupied now
        return idx

    def _match_prefix(self, prompt):
        """(pool_idx, L) of the longest registered prefix the prompt
        starts with, or (None, 0)."""
        best, best_len = None, 0
        pt = tuple(int(t) for t in prompt)
        for i, pref in enumerate(self._prefixes):
            if len(pref) > best_len and len(pref) <= len(pt) \
                    and pt[:len(pref)] == pref:
                best, best_len = i, len(pref)
        return best, best_len

    @property
    def _supports_seed(self):
        # mirrors _supports_temperature: a seed names a per-request
        # sampling stream, which only exists on the sampled tick — the
        # greedy tick never draws and the speculative engine pins its
        # own draft/verify streams, so a seed there would be silently
        # ignored; reject it at submission instead (ADVICE r5)
        return self.temperature > 0.0 and self.draft is None

    @property
    def _supports_temperature(self):
        # the sampled tick graph only exists when the engine was built
        # sampled; a greedy engine has no per-request override point
        return self.temperature > 0.0 and self.draft is None

    def _admit(self, rid, prompt, max_new_tokens, eos_token_id,
               seed=None, temperature=None):
        slot = self._free.pop()
        self._slot_temp = self._slot_temp.at[slot].set(
            float(self.temperature if temperature is None
                  else temperature))
        # sampling stream: domain-separated so an explicit seed can
        # never collide with an auto rid.  Default (seed=None) keys off
        # the rid — deterministic given the SUBMISSION ORDER; an
        # explicit seed gives a request-intrinsic stream independent of
        # everything else (the batch-independence contract)
        base = jax.random.fold_in(self._key, 0 if seed is None else 1)
        self._slot_keys = self._slot_keys.at[slot].set(
            jax.random.fold_in(base, rid if seed is None else seed))
        row = np.zeros((self.buf_len,), np.int32)
        row[:len(prompt)] = prompt
        pidx, L = (self._match_prefix(prompt) if self._prefixes
                   else (None, 0))
        if self.rolling:
            self.ids, self.cache = self._prefill_slot_rolling(
                self.ids, self.cache, slot, jnp.asarray(row),
                len(prompt))
        elif pidx is not None:
            # splice: gather the pool row, run only the suffix
            # [L, prompt_len) through decode_chunk on that row, scatter
            # it into the slot
            self.prefix_hits += 1
            self.metrics.counter("engine_prefix_hits_total").inc()
            C = self.prefix_chunk
            for attr, chunk_fn in self._chunk_row.items():
                pool = (self._pool_cache if attr == "cache"
                        else self._pool_d_cache)
                rc = self._take_row(pool, pidx)
                off = L
                while off < len(prompt):
                    # slide the last chunk back instead of shrinking
                    # it: one compiled width, overlap recompute is
                    # idempotent
                    start = min(off, self.buf_len - C)
                    toks = jnp.asarray(row[None, start:start + C])
                    rc = chunk_fn(rc, toks, start)
                    off = start + C
                setattr(self, attr,
                        self._put_row(getattr(self, attr), rc, slot))
            self.ids = self.ids.at[slot].set(jnp.asarray(row))
        else:
            self.ids, self.cache, self.d_cache = self._prefill_slot(
                self.ids, self.cache, self.d_cache, slot,
                jnp.asarray(row))
        self.cur_len = self.cur_len.at[slot].set(len(prompt))
        self.limit = self.limit.at[slot].set(
            min(len(prompt) + max_new_tokens, self.buf_len))
        self._eos = self._eos.at[slot].set(
            -1 if eos_token_id is None else int(eos_token_id))
        self._by_slot[slot] = _Request(rid, slot, len(prompt),
                                       max_new_tokens, eos_token_id)

    def _check_prompt(self, prompt):
        if len(prompt) < 1 or len(prompt) >= self.buf_len:
            raise ValueError(f"prompt length {len(prompt)} not in "
                             f"[1, {self.buf_len})")

    def step(self) -> Dict[int, Any]:
        """One batched decode dispatch — a WINDOW of ``window``
        in-graph decode ticks.  Returns {request_id: [tokens]} for
        every live request that emitted this window (1..window tokens
        on the plain path, 1..gamma+1 under speculative decoding);
        finished requests free their slot (their last token, EOS
        included, is still reported and recorded) and queued arrivals
        admit at the window boundary."""
        if not self._by_slot and self._waiting:
            # cancel() can free every slot without draining the queue
            # (unlike _finish, which drains via _harvest); admit here so
            # queued requests never strand on an idle engine
            self._drain_queue()
        if not self._by_slot:
            return {}
        t0 = self._clock()
        live = list(self._by_slot)
        with maybe_span("engine_window_decode", window=self.window,
                        live=len(live)):
            if self.draft is not None:
                old_len = np.asarray(self.cur_len)
                (self.ids, self.cur_len, self.cache,
                 self.d_cache) = self._sstep(self.ids, self.cur_len,
                                             self.limit, self.cache,
                                             self.d_cache)
                new_len = np.asarray(self.cur_len)
                rows = np.asarray(self.ids)
                emitted = {slot: [int(t) for t in
                                  rows[slot,
                                       old_len[slot]:new_len[slot]]]
                           for slot in self._by_slot}
            else:
                (self.ids, self.cur_len, self.cache, self._slot_keys,
                 toks, valid) = self._step_k(self.ids, self.cur_len,
                                             self.cache,
                                             self._slot_keys,
                                             self._slot_temp,
                                             self.limit, self._eos)
                # THE host sync: one fetch per window, not per token
                toks_h, valid_h = jax.device_get((toks, valid))
                emitted = {slot: [int(t) for t, v
                                  in zip(toks_h[slot], valid_h[slot])
                                  if v]
                           for slot in live}
        return self._harvest(emitted, t0)

    def _out_of_budget(self, req):
        return (len(req.generated) >= req.max_new
                or req.prompt_len + len(req.generated) >= self.buf_len)

    def _freeze_slot(self, slot):
        self.limit = self.limit.at[slot].set(0)

    def _kv_buffers(self):
        bufs = [self.cache]
        for attr in ("d_cache", "_pool_cache", "_pool_d_cache"):
            buf = getattr(self, attr, None)
            if buf is not None:
                bufs.append(buf)
        return bufs

    def _kv_usage(self):
        """Per-slot / per-pool-row KV occupancy, from host mirrors
        only: a live request's used positions are ``prompt_len +
        len(generated)`` (the exact host twin of the device
        ``cur_len``), capped at the slot's position capacity —
        ``buf_len``, or the ring width for a rolling engine (the ring
        never holds more than W positions, so a long request *fully*
        uses its O(window) row).  Slot and draft caches share the same
        position axis, so one per-position byte price covers both."""
        cap = self._window if self.rolling else self.buf_len
        slot_bytes = _tree_nbytes(self.cache)
        if getattr(self, "d_cache", None) is not None:
            slot_bytes += _tree_nbytes(self.d_cache)
        per_pos = slot_bytes / (self.slots * cap) if self.slots else 0.0
        row_bytes = int(round(per_pos * cap))
        slots = []
        for slot in range(self.slots):
            req = self._by_slot.get(slot)
            used_pos = (min(req.prompt_len + len(req.generated), cap)
                        if req is not None else 0)
            used_b = int(round(per_pos * used_pos))
            slots.append({"slot": slot,
                          "rid": req.rid if req is not None else None,
                          "used_positions": used_pos,
                          "capacity_positions": cap,
                          "used_bytes": used_b,
                          "kv_waste_bytes": row_bytes - used_b})
        pools = []
        if getattr(self, "prefix_pool", 0):
            pool_bytes = _tree_nbytes(self._pool_cache)
            if self._pool_d_cache is not None:
                pool_bytes += _tree_nbytes(self._pool_d_cache)
            per_pool_pos = pool_bytes / (self.prefix_pool * self.buf_len)
            pool_row = int(round(per_pool_pos * self.buf_len))
            for i in range(self.prefix_pool):
                used_pos = (min(len(self._prefixes[i]), self.buf_len)
                            if i < len(self._prefixes) else 0)
                used_b = int(round(per_pool_pos * used_pos))
                pools.append({"row": i, "used_positions": used_pos,
                              "capacity_positions": self.buf_len,
                              "used_bytes": used_b,
                              "kv_waste_bytes": pool_row - used_b})
        return slots, pools

    def compile_census(self) -> Dict[str, str]:
        census: Dict[str, str] = {}
        census["engine._prefill_slot_rolling" if self.rolling
               else "engine._prefill_slot"] = "admission"
        census["engine._sstep" if self.draft is not None
               else "engine._step_k"] = "decode"
        if self.prefix_pool > 0:
            census["engine._seed_pool"] = "register_prefix"
            census["engine._take_row"] = "prefix_admission"
            census["engine._put_row"] = "prefix_admission"
            census["engine._chunk_row"] = "prefix_admission"
            if self.draft is not None:
                census["engine._chunk_row_draft"] = "prefix_admission"
        return census

    def stats(self) -> Dict[str, Any]:
        """Base snapshot plus prefix-cache effectiveness: splice
        admissions so far and the hit rate over all admissions (0.0 on
        an engine with no admissions yet or no prefix pool)."""
        s = super().stats()
        s["prefix_hits"] = self.prefix_hits
        s["prefix_hit_rate"] = (self.prefix_hits / s["admitted"]
                                if s["admitted"] else 0.0)
        return s


class PagedEngine(_SlotScheduler):
    """Paged-KV continuous-batching engine (ROADMAP item 1): the
    fixed-slot ``Engine``'s admission/KV architecture replaced by a
    BLOCK-POOL cache plus iteration-level scheduling, in the
    PagedAttention (arXiv:2309.06180) / ORCA shape adapted to XLA's
    static-shape world.

    - KV lives in ONE pool of ``num_blocks`` fixed-size blocks per
      cache leaf (``(num_blocks, Hkv, block_size, D)``); each slot owns
      a per-request BLOCK TABLE — a static-shape ``(max_blocks,)``
      int32 row of physical block ids (padded; ``n_blk`` says how many
      are real).  A request reserves ``ceil(min(prompt+max_new,
      buf_len) / block_size)`` blocks at admission (so an admitted
      request can never deadlock mid-decode) and the device RECYCLES
      them in-graph the tick it hits eos/max-tokens — not at the
      window boundary, not at the next host sync.
    - Prefill is CHUNKED and interleaved with decode inside the same
      ``lax.scan`` window: an admitted slot advances ``kv_len`` by
      ``prefill_chunk`` positions per tick (under a ``lax.cond`` so a
      decode-only steady state never pays the chunk-width forward)
      until it is decode-ready, while other slots keep decoding.
    - Admission happens at the ITERATION boundary: ``step()`` stages
      the waiting queue's head-of-line requests into a static-shape
      ``pending`` pack, and each scan tick admits at most one of them
      into a free slot the moment the block budget allows — a request
      freed at tick t can hand its blocks to the next request at tick
      t+1 of the SAME window.

    Everything stays in-graph with static shapes: the gather
    (``pool[tables]`` -> a dense per-slot view fed to the models'
    unmodified ``decode_chunk``), the column scatter back into the
    pool, the free-stack push/pop, and the admission writes — so the
    zero-retrace steady-state contract holds exactly as for the fixed
    engine (one trace per entry at warmup, delta == 0 forever after).
    Causality makes the dense view exact: positions a slot has not
    written (or stale junk from a previous tenant of a recycled block)
    sit at indices > its current position and the models' causal mask
    zeroes them out of every softmax, so when ``block_size`` divides
    ``buf_len`` the attention computation is bit-identical to the
    fixed-slot engine's and the token-for-token exactness contract
    (vs ``generate_cached`` and vs ``Engine``) carries over — greedy
    AND explicit-seed sampled (same per-request fold_in streams,
    advanced once per own decode tick).

    Donation: ``ids``, the block pool and the RNG key table are
    donated; ``cur_len``/``kv_len``/``n_blk`` are per-slot length
    vectors on ``DONATION_BLOCKLIST`` (the PR 2 compile-cache
    corruption class) and the scheduler vectors (tables, free stack,
    limits) are cheap enough that donating them buys nothing.

    Not wired (use ``Engine``): speculative drafts, rolling windows,
    prefix pools — the splice/ring relayouts are row-granular and the
    paged pool is block-granular."""

    admission_mode = "paged"

    def __init__(self, model, params, slots: int, buf_len: int,
                 block_size: int = 16, num_blocks: Optional[int] = None,
                 prefill_chunk: int = 16, cache_dtype=None,
                 temperature: float = 0.0, top_k=None, top_p=None,
                 rng=None, window: int = 1,
                 metrics: Optional[MetricsRegistry] = None):
        """``block_size`` is the KV positions per block (pick it so it
        divides ``buf_len``: the dense gather width is then exactly
        ``buf_len`` and the attention math is bit-identical to the
        fixed-slot engine; any size stays exact via the causal mask,
        but a non-divisor pads the gather).  ``num_blocks`` is the pool
        capacity (default ``slots * ceil(buf_len / block_size)`` — the
        fixed-slot worst case; the paged win comes from setting it
        LOWER than that and admitting more slots, since real mixed
        traffic rarely reserves full buffers).  ``prefill_chunk`` is
        the positions one prefill tick advances."""
        self.model = model
        self.params = params
        self.slots = slots
        self.buf_len = buf_len
        self.temperature = temperature
        self.window = int(window)
        if self.window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got "
                             f"{block_size}")
        if prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, got "
                             f"{prefill_chunk}")
        self.block_size = int(block_size)
        self.prefill_chunk = int(min(prefill_chunk, buf_len))
        # static max-blocks padding: every block table is this wide
        self.max_blocks = -(-buf_len // self.block_size)
        self.num_blocks = (int(num_blocks) if num_blocks is not None
                           else slots * self.max_blocks)
        if self.num_blocks < self.max_blocks:
            raise ValueError(
                f"num_blocks={self.num_blocks} cannot hold even one "
                f"full-length request ({self.max_blocks} blocks of "
                f"{self.block_size})")
        self._key = (rng if rng is not None
                     else jax.random.PRNGKey(0))
        # same dropless-MoE batch-independence requirement as Engine
        from .parallel.expert_parallel import ExpertParallelMLP
        for mod in model.modules():
            if (isinstance(mod, ExpertParallelMLP)
                    and mod.capacity_factor < mod.n_experts):
                raise ValueError(
                    f"MoE layer with capacity_factor="
                    f"{mod.capacity_factor} < n_experts="
                    f"{mod.n_experts} can drop tokens depending on "
                    f"batch contents; serve dropless "
                    f"(capacity_factor >= n_experts) to keep requests "
                    f"batch-independent")
        if cache_dtype is None:
            cache_dtype = (model._table(params).dtype
                           if hasattr(model, "_table")
                           else params["wte"]["weight"].dtype)
        # the pool: re-leaf the model's own (1, H, S, D) cache template
        # as (num_blocks, H, block_size, D) — one tree_map, so int8
        # scale sidecars and any future leaf page identically (every
        # leaf's position axis is axis 2 by the models/_cache contract)
        template = model.init_cache(1, dtype=cache_dtype)
        NB, bs = self.num_blocks, self.block_size

        def _pool_leaf(leaf):
            if leaf.ndim != 4:
                raise NotImplementedError(
                    "paged KV needs (B, H, S, D)-shaped cache leaves")
            return jnp.zeros((NB, leaf.shape[1], bs) + leaf.shape[3:],
                             leaf.dtype)

        self.pool = jax.tree_util.tree_map(_pool_leaf, template)
        MB = self.max_blocks
        self.ids = jnp.zeros((slots, buf_len), jnp.int32)
        self.cur_len = jnp.zeros((slots,), jnp.int32)
        # prompt positions whose KV is already written; a slot is
        # decode-ready when kv_len == cur_len - 1 (the decode tick
        # itself computes position cur_len - 1)
        self.kv_len = jnp.zeros((slots,), jnp.int32)
        self.limit = jnp.zeros((slots,), jnp.int32)
        self._eos = jnp.full((slots,), -1, jnp.int32)
        self.tables = jnp.zeros((slots, MB), jnp.int32)
        self.n_blk = jnp.zeros((slots,), jnp.int32)
        # LIFO free stack: free_stack[:free_top] are the free block ids
        self.free_stack = jnp.arange(NB, dtype=jnp.int32)
        self.free_top = jnp.int32(NB)
        # host mirrors (refreshed from the one per-window fetch /
        # mutated by the host-side admission paths): block headroom for
        # admission control and per-slot holdings for the ledger
        self._free_top_h = NB
        self._slot_nblk_h: Dict[int, int] = {}
        self._stream_keys_memo: Dict[int, Any] = {}
        self._n_midwindow = 0
        self._slot_keys = jax.vmap(
            lambda i: jax.random.fold_in(self._key, i))(
            jnp.arange(slots))
        self._slot_temp = jnp.full((slots,), float(temperature),
                                   jnp.float32)
        self._init_scheduler(slots, metrics)
        self.metrics.gauge(
            "engine_kv_blocks_total",
            help="KV pool capacity in blocks").set(float(NB))

        S_d = MB * bs            # dense gather width per slot
        C = self.prefill_chunk
        K = self.window
        n_slots = slots

        @jax.named_scope("paged.gather")
        def _gather_dense(pool, tables):
            """pool leaves -> per-slot dense (slots, H, MB*bs, D)
            views through the block tables (stale/padded table entries
            gather junk that the causal mask drops)."""
            def g(leaf):
                d = leaf[tables]                # (slots, MB, H, bs, D)
                d = d.transpose(0, 2, 1, 3, 4)
                return d.reshape(n_slots, leaf.shape[1], S_d,
                                 leaf.shape[3])
            return jax.tree_util.tree_map(g, pool)

        @jax.named_scope("paged.scatter")
        def _scatter_cols(pool, dense, tables, q, gate):
            """Write the freshly computed columns ``q`` (slots, L) of
            the dense views back into their physical blocks.  Gated:
            lanes with ``gate`` False scatter to index num_blocks and
            ``mode='drop'`` discards them — a freed block that was
            already re-handed to another request must never see a
            stale write."""
            blk = jnp.clip(q // bs, 0, MB - 1)
            phys = jnp.take_along_axis(tables, blk, axis=1)
            phys = jnp.where(gate, phys, NB).reshape(-1)
            off = (q % bs).reshape(-1)
            qc = jnp.clip(q, 0, S_d - 1)

            def s(pl, dl):
                H, Dp = pl.shape[1], pl.shape[3]
                idx = jnp.broadcast_to(
                    qc[:, None, :, None],
                    (n_slots, H, qc.shape[1], Dp))
                cols = jnp.take_along_axis(dl, idx, axis=2)
                vals = cols.transpose(0, 2, 1, 3).reshape(-1, H, Dp)
                return pl.at[phys, :, off, :].set(vals, mode="drop")

            return jax.tree_util.tree_map(s, pool, dense)

        def _pop_blocks(free_stack, free_top, n_need):
            """Top n_need entries of the free stack as a padded
            (max_blocks,) table row (static shape; unpopped lanes 0)."""
            j = jnp.arange(MB)
            src = jnp.clip(free_top - 1 - j, 0, NB - 1)
            return jnp.where(j < n_need, free_stack[src], 0)

        def _paged_step_k(ids, cur_len, kv_len, pool, keys, temps,
                          limit, eos, tables, n_blk, free_stack,
                          free_top, pending):
            """K continuous-batching ticks in-graph: each tick runs
            admission (at most one staged request into a freed slot,
            block budget permitting), one chunked-prefill advance for
            every not-yet-ready slot (under a cond — decode-only
            steady state skips it), one decode tick for every ready
            slot, and the in-graph block recycling of slots that died
            this tick.  Emits the (slots, K) token/validity buffers
            plus a (K,) admitted-slot vector the host replays."""
            p_count = pending["count"]

            def tick(carry, _):
                (ids, cur_len, kv_len, pool, keys, temps, limit, eos,
                 tables, n_blk, free_stack, free_top, p_next) = carry
                # -- admission at the iteration boundary --------------
                i = jnp.clip(p_next, 0, n_slots - 1)
                n_need = pending["n_need"][i]
                free_slot = limit == 0
                can = ((p_next < p_count) & jnp.any(free_slot)
                       & (free_top >= n_need))
                slot = jnp.argmax(free_slot).astype(jnp.int32)
                onehot = (jnp.arange(n_slots) == slot) & can
                trow = _pop_blocks(free_stack, free_top, n_need)
                tables = jnp.where(onehot[:, None], trow[None, :],
                                   tables)
                free_top = free_top - jnp.where(can, n_need, 0)
                ids = jnp.where(onehot[:, None],
                                pending["ids"][i][None, :], ids)
                cur_len = jnp.where(onehot, pending["len"][i], cur_len)
                kv_len = jnp.where(onehot, 0, kv_len)
                limit = jnp.where(onehot, pending["limit"][i], limit)
                eos = jnp.where(onehot, pending["eos"][i], eos)
                temps = jnp.where(onehot, pending["temps"][i], temps)
                keys = jnp.where(onehot[:, None],
                                 pending["keys"][i][None, :], keys)
                n_blk = jnp.where(onehot, n_need, n_blk)
                p_next = p_next + can.astype(jnp.int32)
                adm = jnp.where(can, slot, -1)

                # -- chunked prefill, interleaved with decode ---------
                alive = cur_len < limit
                needs_pf = alive & (kv_len < cur_len - 1)

                def do_prefill(pool, kv_len):
                    pos0 = jnp.clip(kv_len, 0, buf_len - 1)
                    qs = pos0[:, None] + jnp.arange(C)[None, :]
                    toks = jnp.take_along_axis(
                        ids, jnp.clip(qs, 0, buf_len - 1), axis=1)
                    dense = _gather_dense(pool, tables)
                    with jax.named_scope("paged.attend"):
                        _, dense = model.decode_chunk(params, toks,
                                                      pos0, dense)
                    gate = (needs_pf[:, None]
                            & (qs < (cur_len - 1)[:, None]))
                    pool2 = _scatter_cols(pool, dense, tables, qs,
                                          gate)
                    kv2 = jnp.where(
                        needs_pf,
                        jnp.minimum(kv_len + C, cur_len - 1), kv_len)
                    return pool2, kv2

                pool, kv_len = lax.cond(
                    jnp.any(needs_pf), do_prefill,
                    lambda pool, kv_len: (pool, kv_len), pool, kv_len)

                # -- decode tick for every decode-ready slot ----------
                # re-check against the POST-prefill kv_len: a slot
                # whose last prefill chunk landed this tick decodes in
                # the same tick (the gather below re-reads the freshly
                # scattered pool), so prefill->decode costs no bubble
                dec_ok = alive & (kv_len >= cur_len - 1)
                pos = jnp.maximum(cur_len - 1, 0)
                tok_in = jnp.take_along_axis(
                    ids, jnp.clip(pos, 0, buf_len - 1)[:, None],
                    axis=1)
                dense = _gather_dense(pool, tables)
                with jax.named_scope("paged.attend"):
                    h, dense = model.decode_chunk(params, tok_in, pos,
                                                  dense)
                pool = _scatter_cols(pool, dense, tables, pos[:, None],
                                     dec_ok[:, None])
                logits = _head_logits(model, params, h)[:, 0]
                if temperature > 0.0:
                    from .models import sampling as smp
                    # identical stream discipline to Engine._step_k:
                    # per-request keys advance once per OWN decode tick
                    # (not while prefilling, not after death), so the
                    # sampled output is batch-independent and equal to
                    # the fixed-slot engine's token for token
                    split = jax.vmap(
                        lambda k: jax.random.split(k, 2))(keys)
                    new_keys, subs = split[:, 0], split[:, 1]
                    safe_t = jnp.where(temps > 0, temps, 1.0)
                    scaled = (logits.astype(jnp.float32)
                              / safe_t[:, None])
                    sampled = jax.vmap(
                        lambda k, l: smp.sample_token(
                            k, l, 1.0, top_k=top_k,
                            top_p=top_p))(subs,
                                          scaled).astype(jnp.int32)
                    greedy = jnp.argmax(logits,
                                        axis=-1).astype(jnp.int32)
                    nxt = jnp.where(temps > 0, sampled, greedy)
                    keys = jnp.where(dec_ok[:, None], new_keys, keys)
                else:
                    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                # alive => cur_len < limit <= buf_len, so the write is
                # never out of row (unlike Engine there is no separate
                # can mask — limit already caps at buf_len)
                ids = jax.vmap(
                    lambda row, p, t, c: row.at[p].set(
                        jnp.where(c, t, row[p])))(
                    ids, jnp.minimum(cur_len, buf_len - 1), nxt,
                    dec_ok)
                new_len = jnp.where(dec_ok, cur_len + 1, cur_len)
                # the decode scatter just wrote KV at cur_len-1, so
                # the coverage counter advances with it — without this
                # the next tick would re-"prefill" an already-written
                # position and decode only every other tick
                kv_len = jnp.where(dec_ok, cur_len, kv_len)

                # -- in-graph block recycling on eos/limit ------------
                hit_eos = dec_ok & (eos >= 0) & (nxt == eos)
                died = dec_ok & (hit_eos | (new_len >= limit))
                freed = jnp.where(died, n_blk, 0)
                offs = jnp.cumsum(freed) - freed     # exclusive scan
                jj = jnp.arange(MB)[None, :]
                push = died[:, None] & (jj < n_blk[:, None])
                dest = jnp.where(push,
                                 free_top + offs[:, None] + jj, NB)
                free_stack = free_stack.at[dest.reshape(-1)].set(
                    tables.reshape(-1), mode="drop")
                free_top = free_top + jnp.sum(freed)
                limit = jnp.where(died, 0, limit)
                n_blk = jnp.where(died, 0, n_blk)

                return ((ids, new_len, kv_len, pool, keys, temps,
                         limit, eos, tables, n_blk, free_stack,
                         free_top, p_next),
                        (nxt, dec_ok, adm))

            carry = (ids, cur_len, kv_len, pool, keys, temps, limit,
                     eos, tables, n_blk, free_stack, free_top,
                     jnp.int32(0))
            carry, (toks, valid, adm) = lax.scan(tick, carry, None,
                                                 length=K)
            (ids, cur_len, kv_len, pool, keys, temps, limit, eos,
             tables, n_blk, free_stack, free_top, _) = carry
            return (ids, cur_len, kv_len, pool, keys, temps, limit,
                    eos, tables, n_blk, free_stack, free_top,
                    toks.T, valid.T, adm)

        # donate ids + the pool + the key table; cur_len/kv_len/n_blk
        # are DONATION_BLOCKLIST length vectors (PR 2 compile-cache
        # corruption class) and the rest is read-mostly scheduler state
        self._paged_step_k = instrumented_jit(
            _paged_step_k, "engine._paged_step_k",
            arg_names=PAGED_STEP_K_ARG_NAMES, donate_argnums=(0, 3, 4))

        def _paged_admit(ids, cur_len, kv_len, limit, eos, keys, temps,
                         tables, n_blk, free_stack, free_top, slot,
                         row, plen, lim, eos_id, key, temp, n_need):
            """Window-boundary admission: reserve blocks off the free
            stack and seed the slot's scheduler row.  No prefill here
            — the prompt's KV is written lazily by the chunked-prefill
            ticks inside the next window (that is what lets admission
            cost O(scheduler row) instead of O(full forward))."""
            trow = _pop_blocks(free_stack, free_top, n_need)
            tables = lax.dynamic_update_index_in_dim(tables, trow,
                                                     slot, axis=0)
            free_top = free_top - n_need
            ids = lax.dynamic_update_index_in_dim(ids, row, slot,
                                                  axis=0)
            cur_len = cur_len.at[slot].set(plen)
            kv_len = kv_len.at[slot].set(0)
            limit = limit.at[slot].set(lim)
            eos = eos.at[slot].set(eos_id)
            keys = keys.at[slot].set(key)
            temps = temps.at[slot].set(temp)
            n_blk = n_blk.at[slot].set(n_need)
            return (ids, cur_len, kv_len, limit, eos, keys, temps,
                    tables, n_blk, free_top)

        self._paged_admit = instrumented_jit(
            _paged_admit, "engine._paged_admit",
            arg_names=PAGED_ADMIT_ARG_NAMES, donate_argnums=(0, 5))
        self._set_kv_gauges()

    # -- admission ---------------------------------------------------------
    def _blocks_for(self, prompt, max_new_tokens) -> int:
        """Blocks a request reserves at admission: its FULL budget
        up front (positions through min(prompt+max_new, buf_len)), so
        an admitted request can always run to completion — admission
        control is the only backpressure point, and the engine can
        never deadlock with every slot mid-request and no block to
        grow into."""
        need = min(len(prompt) + max_new_tokens, self.buf_len)
        return -(-need // self.block_size)

    def _stream_key(self, rid, seed):
        """The per-request sampling key — same domain-separated
        fold_in chain as Engine (exactness contract).  Memoized per
        rid so staging the same waiting request across several windows
        hands the device bit-identical key bytes."""
        k = self._stream_keys_memo.get(rid)
        if k is None:
            base = jax.random.fold_in(self._key,
                                      0 if seed is None else 1)
            k = jax.random.fold_in(base, rid if seed is None else seed)
            self._stream_keys_memo[rid] = k
        return k

    @property
    def _supports_seed(self):
        return self.temperature > 0.0

    @property
    def _supports_temperature(self):
        return self.temperature > 0.0

    def _check_prompt(self, prompt):
        if len(prompt) < 1 or len(prompt) >= self.buf_len:
            raise ValueError(f"prompt length {len(prompt)} not in "
                             f"[1, {self.buf_len})")

    def _can_admit_direct(self, prompt, max_new_tokens) -> bool:
        return (bool(self._free) and self._free_top_h
                >= self._blocks_for(prompt, max_new_tokens))

    def add_request(self, prompt, max_new_tokens, eos_token_id=None,
                    seed=None, temperature=None, tenant=None):
        if self._free and self._free_top_h < self._blocks_for(
                prompt, max_new_tokens):
            raise RuntimeError(
                f"no free KV blocks for this request (needs "
                f"{self._blocks_for(prompt, max_new_tokens)}, "
                f"{self._free_top_h} free); use submit() to queue "
                f"until blocks recycle, or grow num_blocks")
        return super().add_request(prompt, max_new_tokens,
                                   eos_token_id, seed, temperature,
                                   tenant=tenant)

    def _admit(self, rid, prompt, max_new_tokens, eos_token_id,
               seed=None, temperature=None):
        n_need = self._blocks_for(prompt, max_new_tokens)
        if self._free_top_h < n_need:
            raise RuntimeError(
                f"no free KV blocks (need {n_need}, have "
                f"{self._free_top_h}); use submit() to queue until "
                f"blocks recycle")
        slot = self._free.pop()
        row = np.zeros((self.buf_len,), np.int32)
        row[:len(prompt)] = prompt
        lim = min(len(prompt) + max_new_tokens, self.buf_len)
        key = self._stream_key(rid, seed)
        self._stream_keys_memo.pop(rid, None)
        (self.ids, self.cur_len, self.kv_len, self.limit, self._eos,
         self._slot_keys, self._slot_temp, self.tables, self.n_blk,
         self.free_top) = self._paged_admit(
            self.ids, self.cur_len, self.kv_len, self.limit,
            self._eos, self._slot_keys, self._slot_temp, self.tables,
            self.n_blk, self.free_stack, self.free_top,
            jnp.int32(slot), jnp.asarray(row), jnp.int32(len(prompt)),
            jnp.int32(lim),
            jnp.int32(-1 if eos_token_id is None else eos_token_id),
            key,
            jnp.float32(self.temperature if temperature is None
                        else temperature),
            jnp.int32(n_need))
        self._free_top_h -= n_need
        self._slot_nblk_h[slot] = n_need
        self._by_slot[slot] = _Request(rid, slot, len(prompt),
                                       max_new_tokens, eos_token_id)

    def _drain_queue(self):
        # FIFO head-of-line semantics (no reordering — a small request
        # must not starve a big one forever): stop at the first queued
        # request that does not fit the current slot/block headroom
        admitted = False
        while (self._free and self._waiting
               and self._free_top_h >= self._blocks_for(
                   self._waiting[0][1], self._waiting[0][2])):
            self._admit_timed(*self._waiting.pop(0), refresh_kv=False)
            admitted = True
        self._set_queue_gauge()
        if admitted:
            self._set_kv_gauges()

    # -- the window --------------------------------------------------------
    def _stage_pending(self):
        """Static-shape pack of the waiting queue's first ``slots``
        requests for in-window admission.  Items STAY in ``_waiting``
        until the device confirms their admission (the ``adm`` replay)
        — so ``take_waiting`` / failover / cancel keep their exact
        semantics for requests the device has not started."""
        wait_rids = {item[0] for item in self._waiting}
        self._stream_keys_memo = {
            r: k for r, k in self._stream_keys_memo.items()
            if r in wait_rids}
        P = self.slots
        n = min(len(self._waiting), P)
        ids = np.zeros((P, self.buf_len), np.int32)
        lens = np.zeros((P,), np.int32)
        lims = np.zeros((P,), np.int32)
        eoss = np.full((P,), -1, np.int32)
        temps = np.zeros((P,), np.float32)
        needs = np.zeros((P,), np.int32)
        keys = jnp.zeros((P, 2), jnp.uint32)
        for i in range(n):
            (rid, prompt, max_new, eos_id, seed,
             temp) = self._waiting[i]
            ids[i, :len(prompt)] = prompt
            lens[i] = len(prompt)
            lims[i] = min(len(prompt) + max_new, self.buf_len)
            eoss[i] = -1 if eos_id is None else int(eos_id)
            temps[i] = float(self.temperature if temp is None
                             else temp)
            needs[i] = self._blocks_for(prompt, max_new)
            keys = keys.at[i].set(self._stream_key(rid, seed))
        return {"count": jnp.int32(n), "ids": jnp.asarray(ids),
                "len": jnp.asarray(lens), "limit": jnp.asarray(lims),
                "eos": jnp.asarray(eoss), "temps": jnp.asarray(temps),
                "keys": keys, "n_need": jnp.asarray(needs)}

    def step(self) -> Dict[int, Any]:
        """One decode window: stage the queue head, run the K
        continuous-batching ticks, fetch tokens + validity + the
        admission trace in ONE host sync, then replay the device's
        tick-by-tick decisions into the host bookkeeping."""
        if not self._by_slot and not self._waiting:
            return {}
        t0 = self._clock()
        live0 = len(self._by_slot)
        pending = self._stage_pending()
        with maybe_span("engine_window_decode", window=self.window,
                        live=live0):
            (self.ids, self.cur_len, self.kv_len, self.pool,
             self._slot_keys, self._slot_temp, self.limit, self._eos,
             self.tables, self.n_blk, self.free_stack, self.free_top,
             toks, valid, adm) = self._paged_step_k(
                self.ids, self.cur_len, self.kv_len, self.pool,
                self._slot_keys, self._slot_temp, self.limit,
                self._eos, self.tables, self.n_blk, self.free_stack,
                self.free_top, pending)
            # THE host sync: tokens, validity, in-window admissions
            # and the block headroom, fetched once per window
            toks_h, valid_h, adm_h, ft_h = jax.device_get(
                (toks, valid, adm, self.free_top))
        self._free_top_h = int(ft_h)
        return self._harvest_paged(toks_h, valid_h, adm_h, t0, live0)

    def _harvest_paged(self, toks_h, valid_h, adm_h, t0, live0):
        """Replay the window's device decisions in tick order: an
        admission at tick t binds the queue head to its slot BEFORE
        that slot's later tokens are harvested, and a death at tick t
        frees the slot before a tick-t' > t admission reuses it — the
        same order the scan applied on device."""
        n_tok = int(valid_h.sum())
        now = self._record_step(t0, tokens=n_tok,
                                capacity=max(live0, 1) * self.window)
        out: Dict[int, Any] = {}
        for t in range(self.window):
            s = int(adm_h[t])
            if s >= 0:
                (rid, prompt, max_new, eos_id, seed,
                 temp) = self._waiting.pop(0)
                req = _Request(rid, s, len(prompt), max_new, eos_id)
                req.t_submit = self._submit_ts.pop(rid, None)
                req.t_admit = now
                if req.t_submit is not None:
                    self._m_queue_wait.observe(
                        max(now - req.t_submit, 0.0))
                self._by_slot[s] = req
                if s in self._free:
                    self._free.remove(s)
                self._slot_nblk_h[s] = self._blocks_for(prompt,
                                                        max_new)
                self._stream_keys_memo.pop(rid, None)
                self._m_admitted.inc()
                self._n_admitted += 1
                self._n_midwindow += 1
                self.metrics.counter(
                    "engine_midwindow_admissions_total",
                    help="requests admitted INSIDE a decode window at "
                         "an iteration boundary (blocks freed by a "
                         "death earlier in the same window, reused "
                         "before it ends)").inc()
                self._set_queue_gauge()
            for s2 in range(self.slots):
                if not valid_h[s2][t]:
                    continue
                req = self._by_slot.get(s2)
                if req is None:
                    continue
                tok = int(toks_h[s2][t])
                req.generated.append(tok)
                out.setdefault(req.rid, []).append(tok)
                if req.t_first is None:
                    req.t_first = now
                self._m_tokens.inc()
                self._n_tokens += 1
                hit = req.eos is not None and tok == req.eos
                if hit or self._out_of_budget(req):
                    # the device already recycled this request's
                    # blocks IN-GRAPH the tick it died; the host only
                    # mirrors the bookkeeping (no _freeze_slot — limit
                    # is zeroed on device too)
                    self._slot_nblk_h.pop(s2, None)
                    self._finish(s2, req)
        self._drain_queue()
        self._set_kv_gauges()
        return out

    def _out_of_budget(self, req):
        return (len(req.generated) >= req.max_new
                or req.prompt_len + len(req.generated) >= self.buf_len)

    def _freeze_slot(self, slot):
        """cancel() of a LIVE request: the device never saw it die, so
        the host releases its blocks eagerly (plain device ops, not a
        jitted entry — cancel is a rare between-windows host API and
        eager ops never touch the compilation ledger)."""
        n = self._slot_nblk_h.pop(slot, 0)
        if n:
            j = jnp.arange(self.max_blocks)
            dest = jnp.where(j < n, self.free_top + j,
                             self.num_blocks)
            self.free_stack = self.free_stack.at[dest].set(
                self.tables[slot], mode="drop")
            self.free_top = self.free_top + jnp.int32(n)
            self._free_top_h += n
        self.limit = self.limit.at[slot].set(0)
        self.n_blk = self.n_blk.at[slot].set(0)

    # -- observability -----------------------------------------------------
    def _kv_buffers(self):
        return [self.pool]

    def _kv_usage(self):
        """PER-BLOCK accounting: a live request's waste is only the
        unfilled tail of its LAST reserved block-set (held blocks *
        block_size minus the positions its cur_len twin occupies);
        unreserved pool blocks surface as one free-pool entry.  This
        is the ledger line the ISSUE gates on: versus the fixed-slot
        engine's whole-row reservations, `kv_waste_bytes` collapses to
        sub-block granularity on mixed-length traffic."""
        pool_bytes = _tree_nbytes(self.pool)
        per_block = (pool_bytes / self.num_blocks
                     if self.num_blocks else 0.0)
        per_pos = per_block / self.block_size
        slots = []
        for slot in range(self.slots):
            req = self._by_slot.get(slot)
            held = (self._slot_nblk_h.get(slot, 0)
                    if req is not None else 0)
            used_pos = (min(req.prompt_len + len(req.generated),
                            held * self.block_size)
                        if req is not None else 0)
            used_b = int(round(per_pos * used_pos))
            held_b = int(round(per_block * held))
            slots.append({"slot": slot,
                          "rid": req.rid if req is not None else None,
                          "blocks_held": held,
                          "used_positions": used_pos,
                          "capacity_positions": held * self.block_size,
                          "used_bytes": used_b,
                          "kv_waste_bytes": held_b - used_b})
        free_blocks = max(self.num_blocks
                          - sum(self._slot_nblk_h.values()), 0)
        pools = [{"row": "free_blocks", "blocks": free_blocks,
                  "used_positions": 0,
                  "capacity_positions": free_blocks * self.block_size,
                  "used_bytes": 0,
                  "kv_waste_bytes": int(round(per_block
                                              * free_blocks))}]
        return slots, pools

    def _set_kv_gauges(self):
        frag = super()._set_kv_gauges()
        self.metrics.gauge(
            "engine_kv_blocks_free",
            help="KV pool blocks not reserved by any live request "
                 "(admission headroom)").set(float(self._free_top_h))
        return frag

    def compile_census(self) -> Dict[str, str]:
        # ONE decode-window graph covers chunked prefill, decode, the
        # in-window admission and the block recycling (they are cond
        # branches / masked lanes of the same scan, all traced at the
        # first call), plus the window-boundary admission entry
        return {"engine._paged_admit": "admission",
                "engine._paged_step_k": "decode"}

    def warmup(self):
        """Pre-compile the full paged census before traffic: one
        request whose prompt spans a chunk boundary (so the
        chunked-prefill + decode + recycling paths of the scan trace)
        plus a second 1-token request (exercising admission again —
        same graphs, and on a 1-slot engine it rides the in-window
        admission path).  Both are scrubbed from ``result()``; see
        ``Engine.warmup`` for the rid/stream caveats."""
        if self._by_slot or self._waiting:
            raise RuntimeError(
                "warmup() needs an idle engine (no live or queued "
                "requests); warm before traffic")
        plen = max(1, min(self.prefill_chunk + 1, self.buf_len - 1))
        r1 = self.add_request([0] * plen, max_new_tokens=1)
        r2 = self.submit([0], max_new_tokens=1)
        while not (self.is_finished(r1) and self.is_finished(r2)):
            self.step()
        self._finished.pop(r1, None)
        self._finished.pop(r2, None)
        return self

    def stats(self) -> Dict[str, Any]:
        """Base snapshot plus the block-pool fields: pool geometry,
        live headroom, and how many
        admissions happened INSIDE a window (the continuous-batching
        win made visible)."""
        s = super().stats()
        s["block_size"] = self.block_size
        s["blocks_total"] = self.num_blocks
        s["blocks_free"] = self._free_top_h
        s["max_blocks_per_request"] = self.max_blocks
        s["midwindow_admissions"] = self._n_midwindow
        return s


class Seq2SeqEngine(_SlotScheduler):
    """Continuous batching for ENCODER-DECODER models (T5 family).

    Decoder-only serving reuses one KV cache per slot; seq2seq serving
    needs two per-slot residents instead: the cross-attention K/V
    precomputed from that request's encoder pass, and a decoder
    self-attention cache.  ``add_request`` runs the encoder for the new
    request alone and scatters both into its slot
    (``T5.init_seq2seq_state`` / ``seed_slot_seq2seq``); ``step()`` is
    one jitted ``decode_step_rows`` tick over all slots at per-slot
    decoder positions — greedy, matching ``T5.generate``'s semantics
    token-for-token for each request regardless of what shares the
    batch (pinned in tests/test_serving.py).

    ``src_len`` fixes the padded source width (requests validate
    against it; shorter sources are masked, exactly like
    ``generate(attention_mask=...)``); ``max_new_cap`` fixes the
    decoder cache width, and per-request ``max_new_tokens`` may be
    anything up to it.  ``submit`` queues FIFO like the decoder-only
    Engine.  ``window=K`` scans K decoder ticks in-graph per
    ``step()`` with the same mid-window EOS/limit freeze and
    once-per-window host fetch as the decoder-only engine.
    """

    def __init__(self, model, params, slots: int, src_len: int,
                 max_new_cap: int, cache_dtype=None, window: int = 1,
                 metrics: Optional[MetricsRegistry] = None):
        self.model = model
        self.params = params
        self.slots = slots
        self.src_len = src_len
        self.max_new_cap = max_new_cap
        self.window = int(window)
        if self.window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        if cache_dtype is None:
            cache_dtype = params["shared"]["weight"].dtype
        self.state = model.init_seq2seq_state(slots, src_len,
                                              max_new_cap, cache_dtype)
        self.out = jnp.zeros((slots, max_new_cap), jnp.int32)
        self.n_new = jnp.zeros((slots,), jnp.int32)
        # per-slot token budget (n_new < s_limit == slot is live; zeroed
        # on finish) and EOS id (-1 = none) for the in-graph masking
        self.s_limit = jnp.zeros((slots,), jnp.int32)
        self._eos = jnp.full((slots,), -1, jnp.int32)
        self._init_scheduler(slots, metrics)

        # donate the slot state: the encoder scatter updates the cross
        # K/V + decoder cache in place instead of duplicating them
        self._seed = instrumented_jit(
            lambda st, slot, row, n: model.seed_slot_seq2seq(
                params, st, slot, row, n),
            "seq2seq._seed", arg_names=("state", "slot", "row", "n"),
            donate_argnums=(0,))

        def _step_k(state, out, n_new, limit, eos):
            """K decoder ticks in-graph; same freeze/validity contract
            as the decoder-only ``_step_k``."""

            def tick(carry, _):
                state, out, n_new, alive = carry
                start = jnp.full((slots,),
                                 model.cfg.decoder_start_token_id,
                                 jnp.int32)
                prev = jnp.take_along_axis(
                    out, jnp.maximum(n_new - 1, 0)[:, None],
                    axis=1)[:, 0]
                tok = jnp.where(n_new == 0, start, prev)
                logits, state = model.decode_step_rows(params, tok,
                                                       n_new, state)
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                can = alive & (n_new < max_new_cap)
                out = jax.vmap(
                    lambda row, p, t, c: row.at[p].set(
                        jnp.where(c, t, row[p])))(
                    out, jnp.minimum(n_new, max_new_cap - 1), nxt, can)
                new_n = jnp.where(can, n_new + 1, n_new)
                emitted = alive
                hit_eos = (eos >= 0) & (nxt == eos)
                alive = alive & ~hit_eos & (new_n < limit)
                return (state, out, new_n, alive), (nxt, emitted)

            alive0 = n_new < limit
            (state, out, n_new, _), (toks, valid) = lax.scan(
                tick, (state, out, n_new, alive0), None,
                length=self.window)
            return state, out, n_new, toks.T, valid.T

        # state + out donated; n_new deliberately not (the per-slot
        # length vector — see the donation note on Engine._step_k)
        self._step_k = instrumented_jit(
            _step_k, "seq2seq._step_k",
            arg_names=SEQ2SEQ_STEP_K_ARG_NAMES, donate_argnums=(0, 1))

    def compile_census(self) -> Dict[str, str]:
        return {"seq2seq._seed": "admission",
                "seq2seq._step_k": "decode"}

    def _kv_buffers(self):
        # per-slot seq2seq state: cross-attention K/V + decoder cache
        return [self.state]

    def _kv_usage(self):
        """Per-slot occupancy over the two seq2seq residents: the
        ``cross`` subtree is cross-attention K/V (used up to the
        request's source length), the ``dec`` subtree is the decoder
        self-attention cache (used up to its generated count);
        remaining per-slot state (e.g. the source mask) counts as used
        while the slot is live.  Classified by the state's own subtree
        keys (``init_seq2seq_state``'s contract) — an axis-value
        heuristic would misclassify whenever ``src_len ==
        max_new_cap`` — with a shape-based fallback for state pytrees
        that don't follow the key convention."""
        if isinstance(self.state, dict) and "cross" in self.state \
                and "dec" in self.state:
            cross = _tree_nbytes(self.state["cross"])
            dec = _tree_nbytes(self.state["dec"])
            other = _tree_nbytes(self.state) - cross - dec
        else:
            cross = dec = other = 0
            for leaf in jax.tree_util.tree_leaves(self.state):
                shape = getattr(leaf, "shape", ())
                nb = getattr(leaf, "nbytes", 0)
                if len(shape) >= 2 and self.src_len in shape[1:]:
                    cross += nb
                elif len(shape) >= 2 and self.max_new_cap in shape[1:]:
                    dec += nb
                else:
                    other += nb
        slots = []
        for slot in range(self.slots):
            req = self._by_slot.get(slot)
            if req is not None:
                src_pos = min(req.prompt_len, self.src_len)
                dec_pos = min(len(req.generated), self.max_new_cap)
                live = 1.0
            else:
                src_pos = dec_pos = 0
                live = 0.0
            used_b = int(round(
                cross * src_pos / (self.slots * self.src_len)
                + dec * dec_pos / (self.slots * self.max_new_cap)
                + other * live / self.slots))
            cap_b = int(round((cross + dec + other) / self.slots))
            slots.append({"slot": slot,
                          "rid": req.rid if req is not None else None,
                          "used_positions": src_pos + dec_pos,
                          "capacity_positions": (self.src_len
                                                 + self.max_new_cap),
                          "used_bytes": used_b,
                          "kv_waste_bytes": cap_b - used_b})
        return slots, []

    def _check_prompt(self, src):
        if len(src) < 1 or len(src) > self.src_len:
            raise ValueError(f"source length {len(src)} not in "
                             f"[1, {self.src_len}]")

    def _admit(self, rid, src, max_new_tokens, eos_token_id,
               seed=None, temperature=None):
        slot = self._free.pop()
        row = np.zeros((self.src_len,), np.int32)
        row[:len(src)] = src
        self.state = self._seed(self.state, slot, jnp.asarray(row),
                                len(src))
        self.n_new = self.n_new.at[slot].set(0)
        max_new = min(max_new_tokens, self.max_new_cap)
        self.s_limit = self.s_limit.at[slot].set(max_new)
        self._eos = self._eos.at[slot].set(
            -1 if eos_token_id is None else int(eos_token_id))
        self._by_slot[slot] = _Request(rid, slot, len(src), max_new,
                                       eos_token_id)

    def step(self) -> Dict[int, Any]:
        """One batched decoder dispatch — a window of ``window``
        in-graph ticks; {rid: [tokens]} for live requests.  Finishes
        on per-request EOS or token budget (frozen mid-window
        in-graph); the slot frees at the window boundary."""
        if not self._by_slot and self._waiting:
            # see Engine.step: cancel() may leave waiting work on an
            # otherwise idle engine
            self._drain_queue()
        if not self._by_slot:
            return {}
        t0 = self._clock()
        live = list(self._by_slot)
        with maybe_span("engine_window_decode", window=self.window,
                        live=len(live)):
            (self.state, self.out, self.n_new, toks,
             valid) = self._step_k(self.state, self.out, self.n_new,
                                   self.s_limit, self._eos)
            # THE host sync: one fetch per window, not per token
            toks_h, valid_h = jax.device_get((toks, valid))
            emitted = {slot: [int(t) for t, v
                              in zip(toks_h[slot], valid_h[slot]) if v]
                       for slot in live}
        return self._harvest(emitted, t0)

    def _out_of_budget(self, req):
        # req.max_new is already min(max_new_tokens, max_new_cap)
        return len(req.generated) >= req.max_new

    def _freeze_slot(self, slot):
        self.s_limit = self.s_limit.at[slot].set(0)
