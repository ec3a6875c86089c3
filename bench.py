"""Benchmark harness: all five BASELINE.md configs + the two north-star
metrics (allreduce bandwidth, fused-optimizer step time).

Prints one JSON line per config.  Every line is self-certifying: backend,
device count, and device kind are embedded (round-2 ADVICE item 1).  It
runs on whatever backend JAX finds and says which; it starts no other
process, and a config that raises makes the exit status non-zero.

vs_baseline on the ResNet-50 amp-O2 DDP lines (BASELINE config #2) is
measured against the driver's north star of 10k images/sec aggregate on
v5e-64 => 156.25 images/sec/chip (BASELINE.md);
the other configs have no published reference numbers (BASELINE.md: the
reference publishes none) so they report vs_baseline: null.

On CPU hosts each config shrinks to a smoke size (a rehearsal of the
control flow; its timings are not device numbers).
"""

import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

BASELINE_IMG_PER_SEC_PER_CHIP = 10_000.0 / 64.0


def require_shard_devices(ndev: int, n: int = 2):
    """The ZeRO bench legs' device-count gate: a bare RuntimeError —
    the same skippable class as the graph-lint entry points — so a
    1-ambient-device host skips the legs instead of failing the run."""
    if ndev < n:
        raise RuntimeError(
            f"the ZeRO legs shard the weight update over the data "
            f"axis; {ndev} ambient device(s) admit no shard split")


def main():
    import jax

    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P

    from apex_tpu import amp, optimizers, parallel, models
    from apex_tpu.nn import functional as F
    from apex_tpu.utils import configure_compile_cache

    # every stdout record is schema-versioned JSONL (observability
    # exporter): schema_version + capture host on every line.
    # tests/ci/check_bench_schema.py validates the stream.
    from apex_tpu.observability.exporters import JsonlExporter

    configure_compile_cache()

    on_tpu = jax.default_backend() == "tpu"
    ndev = len(jax.devices())
    mesh = Mesh(np.array(jax.devices()), ("data",))
    base = {"backend": jax.default_backend(), "ndev": ndev,
            "arch": jax.devices()[0].device_kind}

    def emit(**kw):
        print(json.dumps(JsonlExporter.enrich({**kw, **base})), flush=True)

    def two_level_ici():
        """Devices per ICI slice for the two-level comm legs, or 1 when
        the mesh has no second level.  One process's chips share one
        ICI, so a single-process accelerator run has none; only the CPU
        rehearsal mesh, which has no fabric at all, gets an invented
        split so that the hierarchical code path still runs there."""
        if jax.process_count() > 1:
            return ndev // jax.process_count()
        if jax.default_backend() != "cpu":
            return 1
        return max((d for d in range(2, ndev) if ndev % d == 0),
                   default=1)

    # --fleet N: multi-replica serving-fleet bench — steady-state
    # throughput and per-request tail latency of an N-replica Fleet vs
    # a single replica (same engine shape, same workload), then the
    # same fleet workload with one replica KILLED mid-run by the
    # seeded fault harness (failover cost made visible).  Emits bench
    # metric lines plus `kind: fleet` snapshot records; the whole
    # stream stays check_bench_schema clean.  Runs INSTEAD of the job
    # list (it is an explicit opt-in comparison, not a smoke config)
    # but AFTER --graph-lint, which still gates the exit status.
    # --comm: gradient-allreduce topology microbench — flat vs
    # hierarchical (ICI/DCN two-level) vs bf16-compressed hierarchical
    # on the same bucket.  Per-level wire bytes come from
    # parallel.allreduce_comm_plan (and are ASSERTED against each
    # other: the hierarchical DCN payload must be exactly 1/ici of the
    # flat one, the compressed one exactly half again); wall-clock is
    # reported, never gated — on a CPU smoke host all fabrics are the
    # same memory bus.  PR 14 adds the overlapped-schedule comparison:
    # the same gradient bytes through a staged backward with per-stage
    # bucket reductions issued INSIDE the backward (overlap) vs after
    # it (overlap_off), schedule fields on every attribution record
    # and the comm-hidden delta asserted positive on accelerator
    # backends.  Like --fleet it runs INSTEAD of the job list but
    # AFTER --graph-lint, which still gates the exit status (--fleet
    # takes precedence when both are passed; --profile COMPOSES — see
    # below).
    # --numerics: numerics-instrumentation overhead per opt-level —
    # the SAME DDP resnet18 train step timed with the NumericsMonitor
    # on vs off (per-layer grad health + per-bucket stats + divergence
    # digest vs nothing), plus one `kind: numerics` gradient-health
    # record per level from the instrumented run's flush.
    # --run: operational-plane bench — (1) training-run supervisor
    # overhead: the SAME DDP resnet18 O2 loop with the host-side
    # RunSupervisor observing every step's already-fetched loss vs not
    # observing (the jitted step is identical by the audit-pinned
    # wrap_step contract — only the host-side observe cost can differ),
    # plus the loop's `kind: run` verdict record; (2) fleet SLO/goodput:
    # a deadline-carrying fleet workload emitting
    # goodput_tokens_per_s + the `kind: fleet` record with the SLO
    # fields.
    # --chaos: self-healing controllers under seeded faults on a
    # DETERMINISTIC tick clock (every fleet step advances the injected
    # clock by exactly one "tick", so deadlines, queue waits, MTTR and
    # attainment are step-counted and reproducible): (1) a seeded
    # traffic spike served with NO controller vs with the SLO-feedback
    # controller (fleet.autoscale.SloController actuating the
    # admission bound) — the chaos_spike_* lines carry p99 latency,
    # deadline attainment and goodput per tick; (2) a seeded replica
    # death mid-run — the chaos_mttr_* line carries the fleet's
    # failover→first-progress MTTR; (3) a PLANNED preemption of an
    # elastic training run (SIGTERM-shaped, injected via the
    # TrainingFaults preemption window into a PreemptionGuard): the
    # run takes its coordinated emergency snapshot (model tree + data
    # cursor under one checksum) at the step boundary, exits
    # `preempted`, a fresh trainer resumes from it, and the bench
    # ASSERTS the resumed loss trajectory and consumed-sample-index
    # sequence are identical to an undisturbed run before emitting the
    # trend-gated chaos_preempt_resume overhead/MTTR line; plus the
    # `kind: recovery` and `kind: fleet` records, all schema-v7 gated.
    # --profile: device-time truth (PR 13) — capture the O2 DDP train
    # step (flat vs hierarchical gradient comm) and the windowed
    # decode engine under jax.profiler, parse the Chrome trace with
    # observability.timeline, and emit `kind: profile` records whose
    # overlap_fraction is MEASURED from kernel-interval overlap on the
    # device timeline (not host-differenced): the comm-visible ms per
    # topology is ROADMAP item 2's baseline line, and the engine
    # record carries the KV fragmentation pair (kv_waste_bytes +
    # kv_utilization) item 1's paged allocator must drive down.
    # Precedence when combined: --fleet > --comm > --numerics
    # > --run > --chaos > --profile; --graph-lint composes with all of
    # them and still gates the exit status.  EXCEPTION (PR 14):
    # --comm --profile COMPOSE — the comm bench additionally captures
    # the flat and overlapped train steps under jax.profiler and emits
    # kind: profile records, so comm_visible_ms is MEASURED on the
    # same executables the attribution differenced (the mode-
    # precedence chain used to silently drop --profile there).
    comm_flag = "--comm" in sys.argv
    numerics_flag = "--numerics" in sys.argv
    run_flag = "--run" in sys.argv
    chaos_flag = "--chaos" in sys.argv
    profile_flag = "--profile" in sys.argv

    fleet_n = 0
    if "--fleet" in sys.argv:
        idx = sys.argv.index("--fleet")
        try:
            fleet_n = int(sys.argv[idx + 1])
        except (IndexError, ValueError):
            raise SystemExit("bench: --fleet needs an integer replica "
                             "count (e.g. --fleet 2)")
        if fleet_n < 1:
            raise SystemExit(f"bench: --fleet must be >= 1, got "
                             f"{fleet_n}")

    def run_fleet_bench():
        from apex_tpu import serving
        from apex_tpu.fleet import FaultyReplica, Fleet, RetryPolicy
        from apex_tpu.observability import compilation as obscomp

        cfg = models.GPTConfig(vocab_size=128, block_size=32,
                               n_layer=2, n_head=4, n_embd=32,
                               dropout=0.0)
        model = models.GPT(cfg)
        params, _ = model.init(jax.random.PRNGKey(0))
        params = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16)
            if x.dtype == jnp.float32 else x, params)
        slots, prompt_len, new_tokens = 4, 4, 16
        requests = 32 * max(fleet_n, 2)
        rounds = 4

        def _round(x, nd=4):
            return None if x is None else round(x, nd)

        ledger = obscomp.get_ledger()

        def build_fleet(n_replicas, inject_death=False):
            """Build AND warm: ``Fleet.warmup()`` pre-compiles every
            replica's closures (each Engine instance re-jits its own),
            so the compile cost is measured HERE as cold_compile_ms
            instead of smearing N compiles across the first timed
            windows — the PR 4 gotcha fixed at the source.  Returns
            (fleet, replicas, cold_compile_ms, compiles)."""
            traces0 = ledger.total_traces()
            wall0 = ledger.compile_wall_s()
            reps = [serving.Engine(model, params, slots=slots,
                                   buf_len=cfg.block_size)
                    for _ in range(n_replicas)]
            if inject_death:
                reps[0] = FaultyReplica(reps[0])
            # a replica death burns one attempt per failover plus one
            # per sacrificed half-open probe; the default budget of 4
            # can strand a request mid-bench, which would understate
            # the failover story — give requests room to survive it.
            # step_workers=1 FORCES the serial loop the emitted note
            # describes: this comparison isolates orchestration cost,
            # and on a shared-CPU host threaded replicas oversubscribe
            # the XLA intra-op pool and corrupt the measurement
            fl = Fleet(reps, policy="least_loaded",
                       max_queue=2 * requests,
                       retry=RetryPolicy(max_attempts=10),
                       step_workers=1)
            fl.warmup()
            cold_ms = (ledger.compile_wall_s() - wall0) * 1e3
            return (fl, reps, cold_ms,
                    ledger.total_traces() - traces0)

        def measure(fl, n_requests=None):
            """One saturated pass of the workload; returns
            (tokens/sec, sorted per-request latencies)."""
            rng = np.random.RandomState(0)
            rids = [fl.submit(
                list(rng.randint(0, cfg.vocab_size, prompt_len)),
                max_new_tokens=new_tokens)
                for _ in range(n_requests or requests)]
            tok0 = fl.stats()["tokens_generated"]
            t0 = time.perf_counter()
            while fl.live():
                fl.step()
            dt = time.perf_counter() - t0
            lat = sorted(fl.latency(r) for r in rids
                         if fl.status(r) == "finished")
            return (fl.stats()["tokens_generated"] - tok0) / dt, lat

        def pcts(lat):
            if not lat:
                return None, None
            return (lat[len(lat) // 2],
                    lat[min(len(lat) - 1, int(len(lat) * 0.99))])

        # Fleet.warmup() inside build_fleet pre-compiles every
        # replica's closures (the compile cost is on the emitted line
        # as cold_compile_ms, never in a timed pass), then one warm
        # traffic pass settles the host caches before the INTERLEAVED
        # best-of-N measured passes: single and fleet alternate, so
        # background-load drift on a shared host hits both sides
        # instead of whichever ran second.
        f_single, _, s_cold_ms, s_compiles = build_fleet(1)
        f_multi, _, f_cold_ms, f_compiles = build_fleet(fleet_n)
        measure(f_single, n_requests=2 * slots)
        measure(f_multi, n_requests=2 * slots * fleet_n)
        # per-SIDE steady-state deltas: each emitted line's
        # steady_state_retraces must cover exactly its own timed
        # passes (the schema's documented meaning), not the other
        # fleet's
        s_retraces = f_retraces = 0
        s_best, f_best = (0.0, []), (0.0, [])
        for _ in range(rounds):
            t = ledger.total_traces()
            s_best = max(s_best, measure(f_single), key=lambda x: x[0])
            s_retraces += ledger.total_traces() - t
            t = ledger.total_traces()
            f_best = max(f_best, measure(f_multi), key=lambda x: x[0])
            f_retraces += ledger.total_traces() - t
        f_single.close()
        f_multi.close()
        (single_tput, s_lat), (tput, f_lat) = s_best, f_best
        s_p50, s_p99 = pcts(s_lat)
        p50, p99 = pcts(f_lat)
        shared_note = (f"best of {rounds} interleaved passes on "
                       f"Fleet.warmup()-warmed fleets (compiles paid "
                       f"up front as cold_compile_ms, never in a "
                       f"timed pass), {requests} requests x "
                       f"{new_tokens} new, {slots} slots/replica, "
                       f"serial stepping; on a shared-CPU host "
                       f"replicas add no compute — the fleet's edge "
                       f"is per-tick cost amortization; real "
                       f"scale-out needs replicas on separate "
                       f"accelerators")
        emit(metric="gpt_tiny_fleet_single_decode_throughput",
             value=round(single_tput, 1), unit="tokens/sec",
             vs_baseline=None, window=1,
             p50_latency_s=_round(s_p50), p99_latency_s=_round(s_p99),
             cold_compile_ms=round(s_cold_ms, 2),
             compiles_total=s_compiles,
             steady_state_retraces=s_retraces,
             note=f"1 replica — the --fleet baseline; {shared_note}")
        emit(metric=f"gpt_tiny_fleet{fleet_n}_decode_throughput",
             value=round(tput, 1), unit="tokens/sec",
             vs_baseline=round(tput / single_tput, 3), window=1,
             p50_latency_s=_round(p50), p99_latency_s=_round(p99),
             cold_compile_ms=round(f_cold_ms, 2),
             compiles_total=f_compiles,
             steady_state_retraces=f_retraces,
             note=f"{fleet_n} replicas, least_loaded; vs_baseline is "
                  f"the fleet/single throughput ratio; {shared_note}")
        emit(**f_multi.record())

        # same workload, one replica killed mid-run: armed AFTER
        # warmup to raise 6 steps into the timed run (a constructor
        # window would fire during warmup and kill the replica before
        # t0); the breaker opens and every reclaimed request restarts
        # on the survivors
        fl_d, reps_d, d_cold_ms, d_compiles = build_fleet(
            fleet_n, inject_death=True)
        measure(fl_d, n_requests=2 * slots * fleet_n)    # warm
        reps_d[0].arm(raise_on_step=(6, None))
        traces_d = ledger.total_traces()
        tput_d, d_lat = measure(fl_d)
        fl_d.close()
        p50_d, p99_d = pcts(d_lat)
        emit(metric=f"gpt_tiny_fleet{fleet_n}_decode_throughput_"
                    f"replica_death",
             value=round(tput_d, 1), unit="tokens/sec",
             vs_baseline=round(tput_d / single_tput, 3), window=1,
             p50_latency_s=_round(p50_d),
             p99_latency_s=_round(p99_d),
             cold_compile_ms=round(d_cold_ms, 2),
             compiles_total=d_compiles,
             steady_state_retraces=ledger.total_traces() - traces_d,
             note=f"{fleet_n} replicas, replica 0 armed to raise 6 "
                  f"steps into the timed run (seeded fault harness): "
                  f"failovers={fl_d.stats()['failovers']}, survivors "
                  f"absorb the reclaimed requests — and recompile "
                  f"NOTHING (steady_state_retraces)")
        emit(**fl_d.record())

        # two-tenant open-loop leg (schema v11): tenant "batch" floods
        # the queue up front at low priority while tenant
        # "interactive" trickles high-priority requests in as the
        # fleet drains — the per-tenant plane must attribute goodput /
        # attainment / queue-wait to each side of exactly this mix.
        # Every request is tagged and deadlined (generously: this leg
        # trends the ACCOUNTING, not CPU latency), so the sum of
        # per-tenant goodput tokens must equal the fleet total — the
        # parity line says the tenant split loses nothing.
        fl_t, _, t_cold_ms, t_compiles = build_fleet(fleet_n)
        deadline_s = 300.0
        n_batch = requests
        n_inter = max(8, requests // 4)
        rng_t = np.random.RandomState(2)

        def _tprompt():
            return list(rng_t.randint(0, cfg.vocab_size, prompt_len))

        traces_t = ledger.total_traces()
        t0 = time.perf_counter()
        for _ in range(n_batch):
            fl_t.submit(_tprompt(), max_new_tokens=new_tokens,
                        deadline=deadline_s, tenant="batch",
                        priority=1)
        sent = 0
        step_i = 0
        while fl_t.live() or sent < n_inter:
            if sent < n_inter and step_i % 4 == 0:
                fl_t.submit(_tprompt(), max_new_tokens=new_tokens,
                            deadline=deadline_s, tenant="interactive",
                            priority=0)
                sent += 1
            fl_t.step()
            step_i += 1
        dt_t = time.perf_counter() - t0
        rec_t = fl_t.record()
        ts_t = fl_t.tenant_stats()["tenants"]
        fl_t.close()
        tenant_tok = sum(b["goodput_tokens"]
                         for b in rec_t["tenants"].values())
        total_tok = rec_t["tokens_within_slo"]
        parity = (tenant_tok / total_tok) if total_tok else None
        t_note = (f"two-tenant open loop: {n_batch} batch requests "
                  f"flood the queue up front, {n_inter} interactive "
                  f"ones trickle in every 4 steps; every request "
                  f"tagged + deadlined ({deadline_s:.0f}s — this leg "
                  f"trends the tenant accounting, not CPU latency); "
                  f"drained in {dt_t:.1f}s")
        for tname in ("interactive", "batch"):
            b = ts_t[tname]
            emit(metric=f"gpt_tiny_fleet{fleet_n}_tenant_{tname}"
                        f"_goodput",
                 value=b["goodput_tokens_per_s"], unit="tokens/sec",
                 vs_baseline=None, tenant=tname,
                 slo_attainment=b["slo_attainment"],
                 goodput_tokens=b["goodput_tokens"],
                 submitted=b["submitted"], shed=b["shed"],
                 deadline_exceeded=b["deadline_exceeded"],
                 queue_wait_p99_s=b["queue_wait"].get("p99"),
                 cold_compile_ms=round(t_cold_ms, 2),
                 compiles_total=t_compiles,
                 steady_state_retraces=(ledger.total_traces()
                                        - traces_t),
                 note=f"tenant {tname!r}; {t_note}")
        emit(metric=f"gpt_tiny_fleet{fleet_n}_tenant_parity",
             value=None if parity is None else round(parity, 4),
             unit="ratio", vs_baseline=None,
             tenants_goodput_tokens=tenant_tok,
             tokens_within_slo=total_tok,
             note=f"sum over tenants of goodput tokens / fleet "
                  f"tokens_within_slo — every request is tagged, so "
                  f"anything but 1.0 means the tenant split lost or "
                  f"double-counted tokens; {t_note}")
        emit(**rec_t)

        # paged-vs-fixed open-loop mixed-length leg (schema v12, the
        # ROADMAP item 1 gate): SAME KV pool bytes on both sides —
        # fixed reserves `slots` whole buf_len rows, paged carves the
        # identical byte pool into blocks and admits 2x the slots —
        # under an open-loop mixed-length arrival stream (lengths the
        # scheduler cannot pick, arrivals it cannot defer), every
        # request deadlined through fleet/slo.py.  The paged engine
        # must win on goodput_tokens_per_s with p99 deadline
        # attainment no worse and TIME-AVERAGED kv_waste_bytes lower;
        # check_bench_trend gates all three on accelerators.
        mixed_n = 48
        deadline_mx = 300.0
        mx_window = 4

        def _mixed_reqs(seed):
            r = np.random.RandomState(seed)
            out = []
            for _ in range(mixed_n):
                plen = int(r.randint(2, cfg.block_size - 4))
                nnew = int(r.randint(2, min(17, cfg.block_size - plen
                                            + 1)))
                out.append((list(r.randint(0, cfg.vocab_size, plen)),
                            nnew))
            return out

        def _mixed_leg(make_engine):
            traces0 = ledger.total_traces()
            wall0 = ledger.compile_wall_s()
            eng = make_engine()
            fl = Fleet([eng], max_queue=4 * mixed_n,
                       retry=RetryPolicy(max_attempts=10),
                       step_workers=1)
            fl.warmup()
            cold_ms = (ledger.compile_wall_s() - wall0) * 1e3
            compiles = ledger.total_traces() - traces0
            reqs = _mixed_reqs(7)
            # settle pass (host caches), then the timed open loop
            for p, nn in reqs[:8]:
                fl.submit(p, max_new_tokens=nn)
            while fl.live():
                fl.step()
            traces_ss = ledger.total_traces()
            waste_samples = []
            sent = 0
            t0 = time.perf_counter()
            while fl.live() or sent < len(reqs):
                # open loop: 2 arrivals per step regardless of
                # completions — mixed lengths hit mid-stream
                for _ in range(2):
                    if sent < len(reqs):
                        p, nn = reqs[sent]
                        fl.submit(p, max_new_tokens=nn,
                                  deadline=deadline_mx, tenant="mixed")
                        sent += 1
                fl.step()
                waste_samples.append(eng.kv_waste_bytes())
            dt = time.perf_counter() - t0
            rec = fl.record()
            st = eng.stats()
            fl.close()
            mean_waste = int(sum(waste_samples)
                             / max(len(waste_samples), 1))
            return {"goodput": rec["goodput_tokens_per_s"],
                    "attainment": rec["slo_attainment"],
                    "mean_waste": mean_waste, "stats": st,
                    "cold_ms": cold_ms, "compiles": compiles,
                    "retraces": ledger.total_traces() - traces_ss,
                    "dt": dt}

        # fixed: 4 slots x 32 positions = 128 pooled KV positions;
        # paged: the SAME 128 positions as 16 blocks of 8, spread over
        # 8 slots — concurrency doubles at identical KV bytes
        fixed_mx = _mixed_leg(
            lambda: serving.Engine(model, params, slots=slots,
                                   buf_len=cfg.block_size,
                                   window=mx_window))
        paged_mx = _mixed_leg(
            lambda: serving.PagedEngine(
                model, params, slots=2 * slots,
                buf_len=cfg.block_size,
                block_size=cfg.block_size // 4,
                num_blocks=slots * 4, prefill_chunk=8,
                window=mx_window))
        assert (fixed_mx["stats"]["kv_cache_bytes"]
                == paged_mx["stats"]["kv_cache_bytes"]), \
            "paged-vs-fixed leg must compare EQUAL KV pool bytes"
        mx_note = (f"open-loop mixed-length leg: {mixed_n} deadlined "
                   f"requests (prompt 2..{cfg.block_size - 5}, "
                   f"2..16 new), 2 arrivals/step, window={mx_window}, "
                   f"EQUAL KV bytes both sides "
                   f"({fixed_mx['stats']['kv_cache_bytes']}B); "
                   f"deadline {deadline_mx:.0f}s trends the SLO "
                   f"accounting, not CPU latency; kv_waste_bytes is "
                   f"the TIME-AVERAGED ledger sample over the loop")
        emit(metric="gpt_tiny_engine_decode_fixed_mixed_goodput",
             value=_round(fixed_mx["goodput"], 1), unit="tokens/sec",
             vs_baseline=None, window=mx_window,
             admission_mode="fixed_slot",
             slo_attainment=_round(fixed_mx["attainment"]),
             kv_cache_bytes=fixed_mx["stats"]["kv_cache_bytes"],
             kv_waste_bytes=fixed_mx["mean_waste"],
             kv_utilization=round(
                 1.0 - fixed_mx["mean_waste"]
                 / max(fixed_mx["stats"]["kv_cache_bytes"], 1), 4),
             cold_compile_ms=round(fixed_mx["cold_ms"], 2),
             compiles_total=fixed_mx["compiles"],
             steady_state_retraces=fixed_mx["retraces"],
             note=f"fixed-slot baseline, {slots} slots x "
                  f"{cfg.block_size}-row reservations; {mx_note}")
        pst = paged_mx["stats"]
        emit(metric="gpt_tiny_engine_decode_paged_mixed_goodput",
             value=_round(paged_mx["goodput"], 1), unit="tokens/sec",
             vs_baseline=(None if not fixed_mx["goodput"] else
                          round(paged_mx["goodput"]
                                / fixed_mx["goodput"], 3)),
             window=mx_window, admission_mode="paged",
             block_size=pst["block_size"],
             blocks_total=pst["blocks_total"],
             blocks_free=pst["blocks_free"],
             midwindow_admissions=pst["midwindow_admissions"],
             slo_attainment=_round(paged_mx["attainment"]),
             kv_cache_bytes=pst["kv_cache_bytes"],
             kv_waste_bytes=paged_mx["mean_waste"],
             kv_utilization=round(
                 1.0 - paged_mx["mean_waste"]
                 / max(pst["kv_cache_bytes"], 1), 4),
             cold_compile_ms=round(paged_mx["cold_ms"], 2),
             compiles_total=paged_mx["compiles"],
             steady_state_retraces=paged_mx["retraces"],
             note=f"paged block pool, {2 * slots} slots over "
                  f"{pst['blocks_total']} blocks of "
                  f"{pst['block_size']} (same bytes as the fixed "
                  f"side's {slots} rows), blocks recycled in-graph at "
                  f"eos + iteration-boundary admission; vs_baseline "
                  f"is paged/fixed goodput; {mx_note}")

        # QoS leg (schema v14, the ROADMAP item 4 gate): the SAME
        # flood-plus-trickle mix as the v11 tenant leg, run twice —
        # once untagged (single-class FIFO fleet, the baseline) and
        # once under a two-class QosPolicy (interactive weight 8,
        # unpreemptible; batch weight 1, tenant->class mapped).  The
        # WFQ plane must hold the interactive class's SLO attainment
        # through the batch flood while the AGGREGATE goodput stays
        # within ~5% of the untagged baseline — priority isolation
        # that taxes total throughput is a regression, not a feature.
        from apex_tpu.fleet import QosClass, QosPolicy

        def _qos_policy():
            return QosPolicy(
                [QosClass("interactive", weight=8, preemptible=False),
                 QosClass("batch", weight=1)],
                tenant_class={"interactive": "interactive",
                              "batch": "batch"})

        def _qos_pass(qos):
            traces0 = ledger.total_traces()
            wall0 = ledger.compile_wall_s()
            fl = Fleet([serving.Engine(model, params, slots=slots,
                                       buf_len=cfg.block_size)
                        for _ in range(fleet_n)],
                       policy="least_loaded", max_queue=2 * requests,
                       retry=RetryPolicy(max_attempts=10),
                       step_workers=1, qos=qos)
            fl.warmup()
            cold_ms = (ledger.compile_wall_s() - wall0) * 1e3
            compiles = ledger.total_traces() - traces0
            rng = np.random.RandomState(3)

            def _p():
                return list(rng.randint(0, cfg.vocab_size,
                                        prompt_len))

            # settle pass (host caches), then the timed open loop —
            # the arrival schedule and every prompt are identical on
            # both passes (same seeded stream, same call order)
            for _ in range(2 * slots):
                fl.submit(_p(), max_new_tokens=new_tokens)
            while fl.live():
                fl.step()
            traces_ss = ledger.total_traces()
            tok0 = fl.stats()["tokens_generated"]
            t0 = time.perf_counter()
            for _ in range(n_batch):
                fl.submit(_p(), max_new_tokens=new_tokens,
                          deadline=deadline_s, tenant="batch")
            sent = 0
            step_i = 0
            while fl.live() or sent < n_inter:
                if sent < n_inter and step_i % 4 == 0:
                    fl.submit(_p(), max_new_tokens=new_tokens,
                              deadline=deadline_s,
                              tenant="interactive")
                    sent += 1
                fl.step()
                step_i += 1
            dt = time.perf_counter() - t0
            tput = (fl.stats()["tokens_generated"] - tok0) / dt
            rec = fl.record()
            cls = fl.tenant_stats()["classes"]
            fl.close()
            return {"tput": tput, "rec": rec, "classes": cls,
                    "cold_ms": cold_ms, "compiles": compiles,
                    "retraces": ledger.total_traces() - traces_ss,
                    "dt": dt}

        base_q = _qos_pass(None)
        qos_q = _qos_pass(_qos_policy())
        q_note = (f"two-class open loop: {n_batch} batch requests "
                  f"flood up front, {n_inter} interactive ones "
                  f"trickle in every 4 steps (identical seeded "
                  f"arrivals as the untagged baseline pass); deadline "
                  f"{deadline_s:.0f}s trends the QoS accounting, not "
                  f"CPU latency; QoS pass drained in "
                  f"{qos_q['dt']:.1f}s vs baseline "
                  f"{base_q['dt']:.1f}s")
        for cname in ("interactive", "batch"):
            b = qos_q["classes"][cname]
            emit(metric=f"gpt_tiny_fleet{fleet_n}_qos_class_{cname}"
                        f"_goodput",
                 value=b["goodput_tokens_per_s"], unit="tokens/sec",
                 vs_baseline=None, qos_class=cname,
                 slo_attainment=b["slo_attainment"],
                 goodput_tokens=b["goodput_tokens"],
                 submitted=b["submitted"], shed=b["shed"],
                 deadline_exceeded=b["deadline_exceeded"],
                 preempted=b["preempted"], weight=b["weight"],
                 queue_wait_p99_s=b["queue_wait"].get("p99"),
                 cold_compile_ms=round(qos_q["cold_ms"], 2),
                 compiles_total=qos_q["compiles"],
                 steady_state_retraces=qos_q["retraces"],
                 note=f"class {cname!r} (weight {b['weight']}) under "
                      f"the two-class policy; {q_note}")
        emit(metric=f"gpt_tiny_fleet{fleet_n}_qos_aggregate_goodput",
             value=round(qos_q["tput"], 1), unit="tokens/sec",
             vs_baseline=(None if not base_q["tput"] else
                          round(qos_q["tput"] / base_q["tput"], 3)),
             cold_compile_ms=round(qos_q["cold_ms"], 2),
             compiles_total=qos_q["compiles"],
             steady_state_retraces=qos_q["retraces"],
             note=f"aggregate decode throughput of the QoS-tagged "
                  f"pass; vs_baseline is qos/untagged — the WFQ "
                  f"plane's overhead, gated at ~5% "
                  f"(check_bench_trend); {q_note}")
        emit(**qos_q["rec"])

        # preemption-exactness episode (schema v14, paged replica):
        # both slots held by batch requests mid-decode, then an
        # interactive submit forces the QoS plane to evict the
        # youngest batch victim, recycle its blocks, and re-queue it
        # from its prompt — the victim's final tokens must equal an
        # undisturbed solo-engine run token-for-token (greedy), and a
        # WARMED fleet must run the whole episode with a
        # compilation-ledger delta of ZERO (eviction is eager
        # host-side slot surgery, never a retrace)
        def _paged_small():
            return serving.PagedEngine(
                model, params, slots=2, buf_len=cfg.block_size,
                block_size=cfg.block_size // 4, num_blocks=8,
                prefill_chunk=4, window=2, temperature=0.0)

        rng_p = np.random.RandomState(5)
        vic_prompt = list(rng_p.randint(0, cfg.vocab_size,
                                        prompt_len))
        oth_prompt = list(rng_p.randint(0, cfg.vocab_size,
                                        prompt_len))
        hi_prompt = list(rng_p.randint(0, cfg.vocab_size,
                                       prompt_len))

        solo_fl = Fleet([_paged_small()], max_queue=8,
                        step_workers=1)
        solo_fl.warmup()
        srid = solo_fl.submit(vic_prompt, max_new_tokens=new_tokens)
        while solo_fl.live():
            solo_fl.step()
        expected = solo_fl.result(srid)
        solo_fl.close()

        fl_p = Fleet([_paged_small()], max_queue=64,
                     retry=RetryPolicy(max_attempts=10),
                     step_workers=1, qos=_qos_policy())
        fl_p.warmup()
        settle = fl_p.submit(vic_prompt, max_new_tokens=new_tokens,
                             tenant="batch")
        while fl_p.live():
            fl_p.step()
        fl_p.result(settle)
        traces_p = ledger.total_traces()
        # oth first, vic second: the victim picker takes the
        # youngest (highest-rid) batch request, so the request we
        # pin against the solo run is the one evicted
        oth = fl_p.submit(oth_prompt, max_new_tokens=new_tokens,
                          tenant="batch")
        vic = fl_p.submit(vic_prompt, max_new_tokens=new_tokens,
                          tenant="batch")
        for _ in range(3):
            fl_p.step()
        hi = fl_p.submit(hi_prompt, max_new_tokens=new_tokens,
                         tenant="interactive")
        while fl_p.live():
            fl_p.step()
        fl_p.result(oth)
        fl_p.result(hi)
        got = fl_p.result(vic)
        pre_n = fl_p.stats()["preemptions"]
        retr_p = ledger.total_traces() - traces_p
        fl_p.close()
        matched = sum(1 for a, b in zip(got, expected) if a == b)
        emit(metric="gpt_tiny_fleet_qos_preemption_parity",
             value=round(matched / max(len(expected), 1), 4),
             unit="ratio", vs_baseline=None,
             matched_tokens=matched,
             expected_tokens=len(expected),
             preemptions=pre_n,
             steady_state_retraces=retr_p,
             note=f"greedy tokens of a preempted-then-readmitted "
                  f"batch request vs an undisturbed solo paged "
                  f"engine: anything but 1.0 means eviction "
                  f"perturbed decode; steady_state_retraces counts "
                  f"ledger traces across the WARMED episode and "
                  f"must be 0 — check_bench_trend gates both on "
                  f"every backend (determinism, not timing)")

    lint_errors = 0
    if "--graph-lint" in sys.argv:
        # prepend static graph-lint findings to the telemetry stream
        # (validated by check_bench_schema.py's dispatching schema):
        # bench certifies throughput, the lint certifies the graphs it
        # measured kept their invariants.  run_lint is the same driver
        # the CLI and CI gate use — summary shape and severity tallies
        # cannot drift.  Entry points tracing an 8-way mesh skip on
        # smaller ambient device counts (plain-CPU smoke hosts).
        from apex_tpu import analysis
        summary = analysis.run_lint(
            emit=lambda rec: print(
                json.dumps(JsonlExporter.enrich(rec)), flush=True),
            skip_runtime_errors=True,
            on_skip=lambda ep, e: print(
                f"bench --graph-lint: skipping {ep.name}: {e}",
                file=sys.stderr))
        lint_errors = summary["errors"]
        print(f"bench --graph-lint: {lint_errors} error(s), "
              f"{summary.get('skipped_entry_points', 0)} skipped "
              f"entry point(s)", file=sys.stderr)
        # the replication ledger rides the same stream (schema v13):
        # one kind: sharding record per shard_map-tracing entry point,
        # so check_bench_trend can ratchet replicated_bytes down as
        # the ZeRO-2/3 stages land.  Statically derived from the
        # already-cached traces — no extra compiles.  Serving engines
        # (no shard_map) and device-count-gated EPs skip via the same
        # bare-RuntimeError class run_lint honors.
        for _ep in analysis.select():
            try:
                rec = analysis.entry_point_sharding_record(_ep)
            except RuntimeError as e:
                if type(e) is not RuntimeError:
                    raise
                continue
            print(json.dumps(JsonlExporter.enrich(rec)), flush=True)

    if fleet_n:
        run_fleet_bench()
        # --graph-lint (if also passed) already ran above and still
        # gates the exit status; the job list is skipped
        return 1 if lint_errors else 0

    def timed(train, state, batch, iters, warmup):
        """sec/step with a hard D2H fetch as the barrier: a host fetch
        of the last step's output cannot complete before the step."""
        for _ in range(warmup):
            state, out = train(state, batch)
        float(jnp.sum(jax.tree_util.tree_leaves(out)[0]))
        t0 = time.perf_counter()
        for _ in range(iters):
            state, out = train(state, batch)
        float(jnp.sum(jax.tree_util.tree_leaves(out)[0]))
        return (time.perf_counter() - t0) / iters

    def make_resnet_step(model, optimizer, ddp):
        def step(state, batch):
            params, bn_state, opt_state = state
            xb, yb = batch

            def loss_fn(p):
                out, new_bn = model.apply(p, xb, state=bn_state, train=True)
                return F.cross_entropy(out, yb), new_bn

            loss, new_bn, grads = amp.scaled_grad(loss_fn, params, opt_state,
                                                  has_aux=True)
            grads = ddp.allreduce_grads(grads)
            params, opt_state, _ = optimizer.step(params, opt_state, grads)
            return (params, new_bn, opt_state), lax.pmean(loss, "data")
        return step

    def sharded(step, donate=True):
        # the state is donated, as DistributedDataParallel.make_step
        # does; donate=False is for harnesses that feed one argument
        # tuple to the program again and again (steptime.attribute_step,
        # timeline.capture)
        return jax.jit(jax.shard_map(
            step, mesh=mesh, in_specs=(P(), (P("data"), P("data"))),
            out_specs=(P(), P()), check_vma=False),
            donate_argnums=(0,) if donate else ())

    def run_comm_bench(profile=False):
        ici = two_level_ici()
        n_stages = 4                      # the overlapped variant's
        # stage count: buffer divisible by stages*ici so neither the
        # stage split nor the shard split pads
        align = n_stages * max(ici, 1)
        n = (25_000_000 if on_tpu else 1_000_000) // align \
            * align                       # no shard padding: the plan
        # relationships below must hold to the byte, not modulo pad
        buf = jnp.ones((n,), jnp.float32)

        def make_train(topo, compress):
            def step(state, batch):
                g = {"g": state[0] + batch[0][0, 0]}
                out = parallel.allreduce_grads_tree(
                    g, "data", comm_topology=topo,
                    allreduce_compress_bf16=compress,
                    ici_size=ici if topo == "hierarchical" else None)
                return (out["g"],), jnp.sum(out["g"][:8])
            return sharded(step)

        variants = [("flat", "flat", False)]
        if ici >= 2:
            variants += [("hier", "hierarchical", False),
                         ("hier_bf16", "hierarchical", True)]
        else:
            print(f"bench --comm: {ndev} device(s) admit no 2-level "
                  f"split; hierarchical variants skipped",
                  file=sys.stderr)
        plans = {}
        for name, topo, compress in variants:
            (b,) = parallel.allreduce_comm_plan(
                {"g": jax.ShapeDtypeStruct((n,), jnp.float32)},
                comm_topology=topo, allreduce_compress_bf16=compress,
                ici_size=ici if topo == "hierarchical" else None,
                world=ndev)
            plans[name] = b
        if "hier" in plans:
            # the whole point of the topology: the slow fabric carries
            # exactly 1/ici of the flat payload, half again compressed
            # — asserted from the plan, not eyeballed from the output
            assert (plans["hier"]["dcn_wire_bytes"] * ici
                    == plans["flat"]["dcn_wire_bytes"]), (
                "hierarchical DCN payload is not 1/ici of flat:",
                plans["hier"], plans["flat"])
            assert (plans["hier_bf16"]["dcn_wire_bytes"] * 2
                    == plans["hier"]["dcn_wire_bytes"]), (
                "bf16 compression did not halve the DCN payload:",
                plans["hier_bf16"], plans["hier"])
        for name, topo, compress in variants:
            b = plans[name]
            # a copy: the step donates its state and buf seeds every
            # variant (and the attribution programs below)
            dt = timed(make_train(topo, compress), (jnp.copy(buf),),
                       (jnp.ones((ndev, 1)), jnp.zeros((ndev, 1))),
                       10, 2)
            emit(metric=f"grad_allreduce_{name}_step_time",
                 value=round(dt * 1e3, 3), unit="ms",
                 vs_baseline=None, comm_topology=b["topology"],
                 compress=compress, ici_size=b["ici_size"],
                 dcn_size=b["dcn_size"], elements=n,
                 wire_bytes=b["wire_bytes"],
                 ici_wire_bytes=b["ici_wire_bytes"],
                 dcn_wire_bytes=b["dcn_wire_bytes"],
                 note=f"{n}-element fp32 gradient bucket over the "
                      f"{ndev}-device data axis; bytes are one "
                      f"replica's on-wire traffic per step from "
                      f"allreduce_comm_plan"
                      + ("; wall-clock on a CPU mesh does not "
                         "separate fabrics — the byte fields are the "
                         "portable signal" if not on_tpu else ""))
        if "hier" in plans:
            emit(metric="grad_allreduce_dcn_bytes_reduction",
                 value=float(ici), unit="x", vs_baseline=None,
                 comm_topology="hierarchical", compress=False,
                 ici_size=plans["hier"]["ici_size"],
                 dcn_size=plans["hier"]["dcn_size"],
                 wire_bytes=plans["hier"]["wire_bytes"],
                 ici_wire_bytes=plans["hier"]["ici_wire_bytes"],
                 dcn_wire_bytes=plans["hier"]["dcn_wire_bytes"],
                 note="flat DCN bytes / hierarchical DCN bytes, "
                      "asserted == ici_size from the comm plan")

        # step-time attribution (observability.steptime): decompose the
        # same DDP train step into compute vs comm time per fabric
        # level — ROADMAP item 2 gates on these numbers, not bytes.
        # Three separately-jitted programs per topology (full step,
        # compute twin via DistributedDataParallel.comm_enabled=False,
        # isolated allreduce), all timed OFF the jitted hot path with
        # the same blocked-fetch barrier as timed() — nothing lands in
        # any jitted graph, so the zero-host-transfer audit holds.
        from apex_tpu.observability import steptime

        def make_attr_step(topo, compress, comm_enabled=True):
            ddp = parallel.DistributedDataParallel(
                comm_topology=topo,
                allreduce_compress_bf16=compress,
                ici_size=ici if topo == "hierarchical" else None)
            ddp.comm_enabled = comm_enabled

            def step(state, batch):
                # a real (if small) compute phase, so the twin
                # subtraction has something to subtract FROM
                g = {"g": state[0] * batch[0][0, 0]
                          + jnp.tanh(state[0])}
                out = ddp.allreduce_grads(g)
                return (out["g"],), jnp.sum(out["g"][:8])
            return sharded(step, donate=False)

        def make_comm_only(topo, compress):
            def step(state, batch):
                out = parallel.allreduce_grads_tree(
                    {"g": state[0]}, "data", comm_topology=topo,
                    allreduce_compress_bf16=compress,
                    ici_size=ici if topo == "hierarchical" else None)
                return (out["g"],), jnp.sum(out["g"][:8])
            return sharded(step, donate=False)

        attr_args = ((buf,),
                     (jnp.ones((ndev, 1)), jnp.zeros((ndev, 1))))
        full_steps = {}
        for name, topo, compress in variants:
            b = plans[name]
            full_steps[name] = make_attr_step(topo, compress)
            att = steptime.attribute_step(
                full_steps[name],
                make_attr_step(topo, compress, comm_enabled=False),
                make_comm_only(topo, compress),
                args=attr_args, plan=[b], iters=10, warmup=2)
            emit(metric=f"train_step_attribution_{name}",
                 value=att["step_ms"], unit="ms", vs_baseline=None,
                 comm_topology=b["topology"], compress=compress,
                 ici_size=b["ici_size"], dcn_size=b["dcn_size"],
                 wire_bytes=b["wire_bytes"],
                 ici_wire_bytes=b["ici_wire_bytes"],
                 dcn_wire_bytes=b["dcn_wire_bytes"],
                 comm_visible_ms=att["comm_ms"],
                 **{k: att[k] for k in steptime.ATTRIBUTION_FIELDS},
                 **{k: att[k]
                    for k in steptime.OVERLAP_SCHEDULE_FIELDS},
                 note="blocked-fetch step decomposition; "
                      "overlap_fraction ~0.0 is today's reduce-after-"
                      "backward baseline, the number ROADMAP item 2 "
                      "(comm/compute overlap) must raise"
                      + ("; CPU mesh: all fabrics share one memory "
                         "bus, level split is byte-proportional"
                         if not on_tpu else ""))

        # -- overlapped schedule (PR 14, ROADMAP item 2): the SAME
        # gradient bytes through a staged backward, reduce-after-
        # backward vs per-stage reductions interleaved with the
        # backward.  Both variants share one stage decomposition and
        # one comm schedule shape, so the only difference the
        # attribution can see is WHEN the buckets are issued — the
        # comm-hidden comparison below is schedule-vs-schedule on the
        # same host, not model-vs-model.
        topo_ov = "hierarchical" if ici >= 2 else "flat"
        m = n // n_stages
        stage_tree = [{"w": jax.ShapeDtypeStruct((m,), jnp.float32)}
                      for _ in range(n_stages)]
        schedules = {
            mode: parallel.overlap_comm_schedule(
                stage_tree, comm_topology=topo_ov,
                ici_size=ici if topo_ov == "hierarchical" else None,
                world=ndev, nproc=1, overlap=(mode == "overlap"))
            for mode in ("overlap", "overlap_off")}
        # the schedule moves issue positions, never payloads: the
        # staged buckets' total wire bytes must equal the monolithic
        # flat/hier bucket's (same elements, no padding by
        # construction)
        ref = plans["hier" if topo_ov == "hierarchical" else "flat"]
        sched_bytes = {k: sum(b[k]
                              for b in schedules["overlap"]["buckets"])
                       for k in ("wire_bytes", "ici_wire_bytes",
                                 "dcn_wire_bytes")}
        assert sched_bytes["wire_bytes"] == ref["wire_bytes"], (
            "staging changed the on-wire payload:", sched_bytes, ref)

        def make_staged(overlap, comm_enabled=True):
            ddp = parallel.DistributedDataParallel(
                comm_topology=topo_ov,
                ici_size=ici if topo_ov == "hierarchical" else None,
                overlap=overlap)
            ddp.comm_enabled = comm_enabled

            def stage_fn(p, a):
                return a * p["w"] + jnp.tanh(a)

            stage_fns = [stage_fn] * n_stages

            def step(state, batch):
                a0 = jnp.full((m,), batch[0][0, 0], jnp.float32)
                loss, grads = ddp.staged_allreduce_grads(
                    stage_fns, lambda a: jnp.sum(a[:8]), state[0], a0)
                return (tuple(grads),), loss
            return sharded(step, donate=False)

        def staged_comm_only(state, batch):
            # share ONE axis-size scalar across the per-stage calls,
            # exactly like staged_allreduce_grads (world_scalar=) —
            # otherwise the isolated-comm program would time S-1
            # scalar rendezvous the measured step never runs,
            # inflating comm_isolated_ms and with it overlap_fraction
            ws = lax.psum(jnp.ones((), jnp.float32), "data")
            outs = []
            for sp in state[0]:
                outs.append(parallel.allreduce_grads_tree(
                    sp, "data", comm_topology=topo_ov,
                    ici_size=ici if topo_ov == "hierarchical"
                    else None, world_scalar=ws))
            return (tuple(outs),), jnp.sum(outs[0]["w"][:8])

        staged_args = ((tuple({"w": jnp.ones((m,), jnp.float32)}
                              for _ in range(n_stages)),),
                       (jnp.ones((ndev, 1)), jnp.zeros((ndev, 1))))
        staged_comm = sharded(staged_comm_only, donate=False)
        staged_atts = {}
        staged_fulls = {}
        for mode in ("overlap_off", "overlap"):
            sched = schedules[mode]
            staged_fulls[mode] = make_staged(mode == "overlap")
            att = steptime.attribute_step(
                staged_fulls[mode],
                make_staged(mode == "overlap", comm_enabled=False),
                staged_comm, args=staged_args,
                plan=sched["buckets"], schedule=sched,
                iters=10, warmup=2)
            staged_atts[mode] = att
            emit(metric=f"train_step_attribution_{mode}",
                 value=att["step_ms"], unit="ms", vs_baseline=None,
                 comm_topology=topo_ov,
                 compress=False,
                 ici_size=sched["buckets"][0]["ici_size"],
                 dcn_size=sched["buckets"][0]["dcn_size"],
                 comm_visible_ms=att["comm_ms"],
                 **sched_bytes,
                 **{k: att[k] for k in steptime.ATTRIBUTION_FIELDS},
                 **{k: att[k]
                    for k in steptime.OVERLAP_SCHEDULE_FIELDS},
                 note=f"staged {n_stages}-stage backward, "
                      + ("per-stage bucket reductions ISSUED inside "
                         "the backward (the overlapped schedule)"
                         if mode == "overlap" else
                         "same stages reduced after the full backward "
                         "(the baseline schedule)")
                      + "; identical buckets and wire bytes — only "
                        "the issue positions differ"
                      + ("; CPU mesh executes collectives "
                         "synchronously, so the schedule win shows "
                         "on async-collective backends" if not on_tpu
                         else ""))
        hidden = (staged_atts["overlap_off"]["comm_ms"]
                  - staged_atts["overlap"]["comm_ms"])
        if on_tpu:
            # the dynamic gate: on hardware with async collectives the
            # overlapped schedule must hide comm (step ~ compute)
            assert staged_atts["overlap"]["comm_ms"] \
                < staged_atts["overlap_off"]["comm_ms"], (
                "overlapped schedule did not reduce visible comm:",
                staged_atts)
        emit(metric="overlap_comm_hidden_delta",
             value=round(hidden, 4), unit="ms", vs_baseline=None,
             comm_visible_overlap_ms=staged_atts["overlap"]["comm_ms"],
             comm_visible_baseline_ms=staged_atts["overlap_off"][
                 "comm_ms"],
             note="reduce-after-backward comm_ms minus overlapped "
                  "comm_ms on the same staged step (positive = the "
                  "schedule hid comm under backward compute); "
                  "asserted positive on accelerator backends, "
                  "reported on CPU smoke where the virtual mesh "
                  "executes collectives synchronously")

        # -- ZeRO weight-update sharding legs (zero1/2/3) ---------------
        # one tiny O2 MLP train step per stage, AOT-compiled once so the
        # memory plan describes the exact executable that was timed;
        # every wire-byte field comes from zero_update_comm_plan and the
        # cross-stage relationships are asserted from the plan, never
        # eyeballed from the output.  Schema v15: each line carries its
        # zero_stage.
        def run_zero_legs():
            require_shard_devices(ndev)
            from apex_tpu import nn
            from apex_tpu.observability import (
                compilation as obscomp, costmodel, memory as obsmem)
            net = nn.Sequential([nn.Flatten(), nn.Linear(64, 64),
                                 nn.ReLU(), nn.Linear(64, 32)])
            model, opt = amp.initialize(
                net, optimizers.FusedAdam(lr=1e-2), opt_level="O2",
                verbosity=0, hard_override=True)
            params, _ = model.init(jax.random.PRNGKey(0))
            B = 8 * ndev
            rng = np.random.RandomState(0)
            batch = (jnp.asarray(rng.randn(B, 64), jnp.float32),
                     jnp.asarray(rng.randint(0, 32, B), jnp.int32))
            stages = [1] + ([2, 3] if ici >= 2 else [])
            if ici < 2:
                print(f"bench --comm: {ndev} device(s) admit no "
                      f"2-level split; zero2/zero3 legs skipped",
                      file=sys.stderr)
            plans = {}
            for stage in stages:
                isz = ici if stage >= 2 else None
                plans[stage] = parallel.zero_update_comm_plan(
                    params, zero_stage=stage, world=ndev,
                    ici_size=isz)
            if len(plans) == 3:
                by_role = {s: {b["role"]: b for b in p}
                           for s, p in plans.items()}
                # the stage-2 point: the DCN carries exactly 1/ici of
                # stage 1's flat-accounted grad payload
                assert (by_role[2]["grad_reduce"]["dcn_wire_bytes"]
                        * ici
                        == by_role[1]["grad_reduce"]["dcn_wire_bytes"]
                        ), (plans[1], plans[2])
                # params never cross the DCN at stages 2/3
                assert all(b["dcn_wire_bytes"] == 0
                           for s in (2, 3) for b in plans[s]
                           if b["role"] != "grad_reduce"), plans
                # the stage-3 point: no param_gather back — only the
                # just-in-time jit_gather, twice (forward + remat
                # replay), at the model HALF dtype (2 bytes/elem, half
                # a would-be fp32 gather)
                assert ({b["role"] for b in plans[3]}
                        == {"grad_reduce", "jit_gather"}), plans[3]
                jg = [b for b in plans[3] if b["role"] == "jit_gather"]
                assert sum(b["eqns"]["all_gather"] for b in jg) == 2
                assert all(b["wire_bytes"] == b["elements"] * 2
                           for b in jg), jg
            ledger = obscomp.get_ledger()
            for stage in stages:
                isz = ici if stage >= 2 else None
                ospecs = amp.zero_optimizer_specs(
                    opt, params, "data", zero_stage=stage,
                    zero_ici_size=isz)
                ost0 = jax.jit(jax.shard_map(
                    lambda p, _s=stage, _i=isz: opt.init(
                        p, zero_axis="data", zero_stage=_s,
                        zero_ici_size=_i),
                    mesh=mesh, in_specs=(P(),), out_specs=ospecs,
                    check_vma=False))(params)

                if stage == 3:
                    def step(ost, bt):
                        xb, yb = bt

                        def loss_fn(m):
                            pp = amp.zero_gather_params(m)
                            out, _ = model.apply(pp, xb, train=True)
                            return F.cross_entropy(out, yb)

                        loss, g = amp.scaled_grad(loss_fn,
                                                  ost.masters, ost)
                        _, ost2, _ = opt.step((), ost, g)
                        return ost2, lax.pmean(loss, "data")
                    state = ost0
                    in_sp = (ospecs, (P("data"), P("data")))
                    out_sp = (ospecs, P())
                else:
                    def step(st, bt):
                        p, ost = st
                        xb, yb = bt

                        def loss_fn(pp):
                            out, _ = model.apply(pp, xb, train=True)
                            return F.cross_entropy(out, yb)

                        loss, g = amp.scaled_grad(loss_fn, p, ost)
                        p2, ost2, _ = opt.step(p, ost, g)
                        return (p2, ost2), lax.pmean(loss, "data")
                    state = (params, ost0)
                    in_sp = ((P(), ospecs), (P("data"), P("data")))
                    out_sp = ((P(), ospecs), P())
                train = jax.jit(jax.shard_map(
                    step, mesh=mesh, in_specs=in_sp, out_specs=out_sp,
                    check_vma=False))
                t0 = time.perf_counter()
                traced = train.trace(state, batch)
                closed, lowered = traced.jaxpr, traced.lower()
                compiled = lowered.compile()
                cold_ms = (time.perf_counter() - t0) * 1e3
                traces_before = ledger.total_traces()
                dt = timed(compiled, state, batch, 10, 2)
                retraces = ledger.total_traces() - traces_before
                assert retraces == 0, (
                    f"zero{stage} timed loop re-traced {retraces}x")
                cost = costmodel.jaxpr_cost(closed)
                plan_mem = obsmem.memory_plan(compiled)
                gb = plans[stage][0]          # the grad_reduce bucket
                wire = {k: sum(b[k] for b in plans[stage])
                        for k in ("wire_bytes", "ici_wire_bytes",
                                  "dcn_wire_bytes")}
                mdtype = cost.dominant_matmul_dtype or "float32"
                metric = f"ddp_mlp_zero{stage}_train_throughput"
                emit(kind="memory", metric=metric, source="compiled",
                     zero_stage=stage, **cost.to_record(), **plan_mem)
                emit(metric=metric, value=round(B / dt / ndev, 1),
                     unit="samples/sec/chip", vs_baseline=None,
                     zero_stage=stage, comm_topology=gb["topology"],
                     compress=False, ici_size=gb["ici_size"],
                     dcn_size=gb["dcn_size"], **wire,
                     flops_per_step=cost.flops,
                     peak_bytes=plan_mem["peak_bytes"],
                     cold_compile_ms=round(cold_ms, 2),
                     compiles_total=1, steady_state_retraces=retraces,
                     **costmodel.mfu(cost.flops, dt, base["arch"],
                                     mdtype),
                     note=f"ZeRO-{stage} sharded weight update on the "
                          f"{ndev}-device axis"
                          + (f" (ici {ici})" if stage >= 2 else
                             " (full-axis shards)")
                          + "; wire bytes from zero_update_comm_plan, "
                            "peak_bytes from the compiled plan of the "
                            "timed executable")

        try:
            run_zero_legs()
        except RuntimeError as e:
            if type(e) is not RuntimeError:
                raise
            print(f"bench --comm: skipping zero legs: {e}",
                  file=sys.stderr)

        if profile:
            # --comm --profile: capture the SAME executables the
            # attribution just timed, so the measured comm-visible ms
            # and overlap fraction describe the programs whose
            # differenced split was emitted above
            from apex_tpu.observability import timeline
            citers = 10 if on_tpu else 3
            for pname, fullfn, fargs in (
                    ("flat", full_steps["flat"], attr_args),
                    ("overlap", staged_fulls["overlap"], staged_args),
                    ("overlap_off", staged_fulls["overlap_off"],
                     staged_args)):
                att = timeline.capture(fullfn, *fargs, iters=citers,
                                       modules=("jit_step",))
                comm_visible = round(
                    max(att["collective_ms"] - att["overlap_ms"],
                        0.0), 4)
                emit(**timeline.profile_record(
                    att, metric=f"comm_profile_{pname}",
                    comm_visible_ms=comm_visible,
                    note=f"device timeline of the {pname} comm-bench "
                         f"step ({citers} warm steps) — the same "
                         f"executable train_step_attribution_{pname} "
                         f"differenced; measured_overlap_fraction is "
                         f"the kernel-interval overlap needle"))
                emit(metric=f"comm_profile_{pname}_comm_visible_ms",
                     value=comm_visible, unit="ms", vs_baseline=None,
                     measured_overlap_fraction=att[
                         "measured_overlap_fraction"],
                     device_busy_ms=att["device_busy_ms"],
                     note=f"collective time NOT hidden under compute "
                          f"on the measured device timeline "
                          f"({pname} comm-bench step)")

    if comm_flag and not fleet_n:
        # --profile composes here instead of being dropped by the
        # precedence chain: kind: profile records for the same
        # executables the attribution times
        run_comm_bench(profile=profile_flag)
        # --graph-lint (if also passed) already ran and still gates
        return 1 if lint_errors else 0

    def run_numerics_bench():
        """Instrumentation-overhead microbench: the ddp_resnet18 train
        step per opt-level, numerics-on vs numerics-off (same model,
        same data, separately jitted), timed with the same blocked-
        fetch barrier as every other config.  The on-run's final carry
        is flushed ONCE at the end — exactly the production cadence —
        and emitted as a ``kind: numerics`` record next to the
        overhead line, so the stream carries both the cost and what it
        bought."""
        from apex_tpu.observability import numerics as obs_numerics

        levels = ("O0", "O1", "O2", "O3") if on_tpu else ("O0", "O2")
        iters, warmup = (30, 5) if on_tpu else (4, 1)
        Bc, image = (32, 96) if on_tpu else (4, 32)
        B = Bc * ndev
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(B, 3, image, image), jnp.float32)
        y = jnp.asarray(rng.randint(0, 10, B), jnp.int32)

        def build(level, enabled):
            model, opt = amp.initialize(
                models.resnet18(num_classes=10),
                optimizers.FusedAdam(1e-3), opt_level=level,
                verbosity=0)
            ddp = parallel.DistributedDataParallel(model)
            params, bn = model.init(jax.random.PRNGKey(0))
            ost = opt.init(params)
            plan = parallel.allreduce_comm_plan(params)
            nm = obs_numerics.NumericsMonitor(
                params, half_dtype="bfloat16",
                bucket_labels=obs_numerics.bucket_labels(plan),
                digest=True, axis_name="data", enabled=enabled)

            def step(state, batch):
                params, bn_s, ost, tele = state
                xb, yb = batch

                def loss_fn(p):
                    out, nb = model.apply(p, xb, state=bn_s,
                                          train=True)
                    return F.cross_entropy(out, yb), nb

                loss, nb, g = amp.scaled_grad(loss_fn, params, ost,
                                              has_aux=True)
                if enabled:
                    nout = []
                    g = ddp.allreduce_grads(g, numerics_out=nout)
                    params, ost2, info = opt.step(params, ost, g,
                                                  grad_health=nm)
                    tele = nm.update(
                        tele, grad_stats=info["grad_health"],
                        bucket_stats=nout,
                        found_inf=info["found_inf"],
                        loss_scale=info["loss_scale"],
                        sync_tree=params)
                else:
                    g = ddp.allreduce_grads(g)
                    params, ost2, _ = opt.step(params, ost, g)
                return ((params, nb, ost2, tele),
                        lax.pmean(loss, "data"))

            return sharded(step), (params, bn, ost, nm.init()), nm, ddp

        def timed_state(train, state, batch):
            """timed() that also returns the final carry (the on-run's
            accumulated numerics state must survive the loop)."""
            for _ in range(warmup):
                state, out = train(state, batch)
            float(jnp.sum(jax.tree_util.tree_leaves(out)[0]))
            t0 = time.perf_counter()
            for _ in range(iters):
                state, out = train(state, batch)
            float(jnp.sum(jax.tree_util.tree_leaves(out)[0]))
            return (time.perf_counter() - t0) / iters, state

        for lvl in levels:
            train_off, state_off, _, _ = build(lvl, False)
            t_off, _ = timed_state(train_off, state_off, (x, y))
            train_on, state_on, nm, ddp = build(lvl, True)
            t_on, final = timed_state(train_on, state_on, (x, y))
            flushed = nm.flush(final[3])
            ddp.record_numerics(flushed)
            overhead = max(t_on - t_off, 0.0)
            emit(metric=f"numerics_overhead_{lvl.lower()}",
                 value=round(overhead * 1e3, 4), unit="ms",
                 vs_baseline=None, opt_level=lvl,
                 step_ms_on=round(t_on * 1e3, 4),
                 step_ms_off=round(t_off * 1e3, 4),
                 overhead_fraction=round(
                     overhead / max(t_off, 1e-9), 4),
                 note=f"resnet18 {lvl} DDP step, NumericsMonitor on "
                      f"vs off ({warmup + iters} steps each); the on "
                      f"variant adds per-layer/per-bucket grad health "
                      f"+ the one-psum divergence digest, zero host "
                      f"syncs (flush happens once, after the loop)"
                      + ("; CPU smoke: wall-clock is noisy, the "
                         "audit-pinned graph deltas are the portable "
                         "signal" if not on_tpu else ""))
            emit(**nm.to_record(
                flushed, metric=f"resnet18_{lvl.lower()}_ddp_numerics",
                opt_level=lvl))

    if numerics_flag and not fleet_n:
        run_numerics_bench()
        # --graph-lint (if also passed) already ran and still gates
        return 1 if lint_errors else 0

    def run_run_bench():
        """Operational-plane bench: supervisor observe-cost on the
        training side, SLO/goodput accounting on the serving side —
        both streams schema-gated (`kind: run` / the v5 fleet fields)
        and trend-gated like every other record family."""
        from apex_tpu import observability as obs

        # -- (1) supervisor overhead on the resnet18 O2 DDP loop ------
        iters, warmup = (30, 5) if on_tpu else (6, 2)
        Bc, image = (32, 96) if on_tpu else (4, 32)
        B = Bc * ndev
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(B, 3, image, image), jnp.float32)
        y = jnp.asarray(rng.randint(0, 10, B), jnp.int32)
        model, opt = amp.initialize(
            models.resnet18(num_classes=10),
            optimizers.FusedAdam(1e-3), opt_level="O2", verbosity=0)
        ddp = parallel.DistributedDataParallel(model)
        params, bn = model.init(jax.random.PRNGKey(0))
        ost = opt.init(params)

        def step(state, batch):
            params, bn_s, ost = state
            xb, yb = batch

            def loss_fn(p):
                out, nb = model.apply(p, xb, state=bn_s, train=True)
                return F.cross_entropy(out, yb), nb

            loss, nb, g = amp.scaled_grad(loss_fn, params, ost,
                                          has_aux=True)
            g = ddp.allreduce_grads(g)
            params, ost2, _ = opt.step(params, ost, g)
            return (params, nb, ost2), lax.pmean(loss, "data")

        state0 = (params, bn, ost)

        def loop(supervise):
            """Identical loop both ways — the per-step loss fetch IS
            an existing flush point and both variants pay it; the on
            variant additionally feeds the supervisor.  wrap_step is
            an identity (audit-pinned), so the jitted program is the
            same object's trace either way."""
            sup = obs.RunSupervisor("bench_resnet18_o2_ddp",
                                    enabled=supervise)
            train = sup.wrap_step(sharded(step))
            st = jax.tree_util.tree_map(jnp.copy, state0)   # donated
            for _ in range(warmup):
                st, loss = train(st, (x, y))
            float(jnp.sum(loss))
            t0 = time.perf_counter()
            t_prev = t0
            for i in range(iters):
                st, loss = train(st, (x, y))
                lval = float(jnp.sum(loss))     # existing flush point
                t_now = time.perf_counter()
                sup.observe_step(step=i, loss=lval,
                                 step_time_s=t_now - t_prev,
                                 comm_stats=ddp.last_comm_stats)
                t_prev = t_now
            return (time.perf_counter() - t0) / iters, sup

        t_off, _ = loop(False)
        t_on, sup = loop(True)
        overhead = max(t_on - t_off, 0.0)
        emit(metric="run_supervisor_overhead_o2",
             value=round(overhead * 1e3, 4), unit="ms",
             vs_baseline=None, opt_level="O2",
             step_ms_on=round(t_on * 1e3, 4),
             step_ms_off=round(t_off * 1e3, 4),
             overhead_fraction=round(overhead / max(t_off, 1e-9), 4),
             note=f"resnet18 O2 DDP step, RunSupervisor observing "
                  f"every step vs disabled ({warmup + iters} steps "
                  f"each); the jitted step is byte-identical by the "
                  f"wrap_step contract (supervisor rule), so this "
                  f"measures pure host-side observe cost"
                  + ("; CPU smoke: wall-clock is noisy, the "
                     "audit-pinned jaxpr identity is the portable "
                     "signal" if not on_tpu else ""))
        emit(**sup.record(metric="resnet18_o2_ddp_run"))

        # -- (2) fleet SLO/goodput ------------------------------------
        from apex_tpu import serving
        from apex_tpu.fleet import Fleet, RetryPolicy

        cfg = models.GPTConfig(vocab_size=128, block_size=32,
                               n_layer=2, n_head=4, n_embd=32,
                               dropout=0.0)
        gmodel = models.GPT(cfg)
        gparams, _ = gmodel.init(jax.random.PRNGKey(0))
        gparams = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16)
            if a.dtype == jnp.float32 else a, gparams)
        slots, prompt_len, new_tokens = 4, 4, 16
        n_requests, n_hopeless = 24, 4
        engines = [serving.Engine(gmodel, gparams, slots=slots,
                                  buf_len=cfg.block_size)
                   for _ in range(2)]

        def build_fleet():
            return Fleet(engines, policy="least_loaded",
                         max_queue=4 * n_requests,
                         retry=RetryPolicy(max_attempts=10),
                         step_workers=1)

        rng = np.random.RandomState(0)

        def submit_all(fl, deadline):
            rids = [fl.submit(
                list(rng.randint(0, cfg.vocab_size, prompt_len)),
                max_new_tokens=new_tokens, deadline=deadline)
                for _ in range(n_requests)]
            # a few requests whose deadline has effectively already
            # passed: the sweep expires them, slo_attainment dips
            # below 1.0 and the goodput excludes their tokens
            rids += [fl.submit(
                list(rng.randint(0, cfg.vocab_size, prompt_len)),
                max_new_tokens=new_tokens, deadline=1e-6)
                for _ in range(n_hopeless)]
            while fl.live():
                fl.step()
            return rids

        # warm on a throwaway fleet (pays the engine compiles), then
        # measure on a FRESH one around the SAME warmed engines: the
        # SloTracker's goodput window opens at first submit, so a
        # shared fleet would fold compile seconds into the trended
        # goodput rate (Fleet is host-side — rebuilding it re-jits
        # nothing)
        warm = build_fleet()
        submit_all(warm, deadline=120.0)
        warm.close()
        fl = build_fleet()
        t0 = time.perf_counter()
        submit_all(fl, deadline=120.0)
        dt = time.perf_counter() - t0
        fl.close()
        rec = fl.record()
        s = fl.stats()
        emit(metric="gpt_tiny_fleet_goodput_tokens_per_s",
             value=rec["goodput_tokens_per_s"], unit="tokens/sec",
             vs_baseline=round(
                 rec["goodput_tokens_per_s"]
                 / max(s["tokens_generated"] / dt, 1e-9), 3),
             slo_attainment=rec["slo_attainment"],
             tokens_within_slo=rec["tokens_within_slo"],
             deadline_exceeded=rec["deadline_exceeded"],
             queue_wait_p50_s=s["slo"]["queue_wait"]["p50"],
             service_p50_s=s["slo"]["service_time"]["p50"],
             note=f"2-replica fleet, {n_requests} requests at a 120s "
                  f"deadline + {n_hopeless} pre-expired; goodput "
                  f"counts only tokens delivered within SLO (the "
                  f"pre-expired requests' would-be tokens don't), "
                  f"vs_baseline is goodput over raw throughput; "
                  f"queue-wait/service split from the same instants "
                  f"the request traces record")
        emit(**rec)

    if run_flag and not fleet_n:
        run_run_bench()
        # --graph-lint (if also passed) already ran and still gates
        return 1 if lint_errors else 0

    def run_chaos_bench():
        """Self-healing bench: a seeded traffic spike with vs without
        the SLO-feedback controller, and a seeded replica death's
        MTTR — all on an injected tick clock so every number is
        step-counted and deterministic (tick = one fleet step; the
        engines still do real decode work, but deadlines, waits and
        MTTR never depend on wall-clock noise)."""
        from apex_tpu import serving
        from apex_tpu.fleet import (AutoscaleConfig, FaultyReplica,
                                    Fleet, FleetOverloaded,
                                    RetryPolicy, SloController)

        cfg = models.GPTConfig(vocab_size=128, block_size=32,
                               n_layer=2, n_head=4, n_embd=32,
                               dropout=0.0)
        gmodel = models.GPT(cfg)
        gparams, _ = gmodel.init(jax.random.PRNGKey(0))
        gparams = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16)
            if a.dtype == jnp.float32 else a, gparams)
        slots, prompt_len, new_tokens = 4, 4, 16
        engines = [serving.Engine(gmodel, gparams, slots=slots,
                                  buf_len=cfg.block_size)
                   for _ in range(2)]

        class _Tick:
            t = 0.0
        clock = lambda: _Tick.t            # noqa: E731

        def build_fleet(inject_death=False):
            reps = list(engines)
            if inject_death:
                reps[0] = FaultyReplica(reps[0])
            return Fleet(reps, policy="least_loaded", max_queue=64,
                         retry=RetryPolicy(max_attempts=10),
                         step_workers=1, clock=clock), reps

        rng = np.random.RandomState(0)

        def prompt():
            return list(rng.randint(0, cfg.vocab_size, prompt_len))

        # seeded spike schedule (tick -> submissions): light steady
        # load, then two 30-request waves.  Wave 1 teaches the
        # controller (misses resolve ~tick 40); wave 2 is where the
        # tightened admission pays — doomed requests shed at submit
        # instead of burning slots on tokens that will miss deadline.
        deadline = 30.0
        waves = {t: 2 for t in range(0, 100, 8)}
        waves[10] = waves.get(10, 0) + 30
        waves[50] = waves.get(50, 0) + 30

        def drive(fl, controller=None, ticks=140):
            # the caller resets _Tick.t/rng BEFORE building the fleet
            # and controller, so their internal t0s sit at tick 0 and
            # every t_s in the records is a non-negative tick offset
            rids, shed = [], 0
            for tick in range(ticks):
                for _ in range(waves.get(tick, 0)):
                    try:
                        rids.append(fl.submit(
                            prompt(), max_new_tokens=new_tokens,
                            deadline=deadline))
                    except FleetOverloaded:
                        shed += 1
                fl.step()
                _Tick.t += 1.0
                if controller is not None and tick % 2 == 1:
                    controller.tick()
            while fl.live():
                fl.step()
                _Tick.t += 1.0
                if controller is not None:
                    controller.tick()
            lat = sorted(fl.latency(r) for r in rids
                         if fl.status(r) == "finished")
            p50 = lat[len(lat) // 2] if lat else None
            p99 = (lat[min(len(lat) - 1, int(len(lat) * 0.99))]
                   if lat else None)
            return rids, shed, p50, p99

        # warm the engine compiles on a throwaway fleet (measured
        # numbers are tick-counted, but a cold compile would still
        # distort nothing — this just keeps the run quick)
        warm, _ = build_fleet()
        for _ in range(2 * slots):
            warm.submit(prompt(), max_new_tokens=new_tokens)
        while warm.live():
            warm.step()
        warm.close()

        # -- (1) spike, no controller vs controller -------------------
        _Tick.t = 0.0
        rng.seed(0)
        fl_base, _ = build_fleet()
        _, shed_b, p50_b, p99_b = drive(fl_base)
        fl_base.close()
        rec_b = fl_base.record()
        base_att = rec_b["slo_attainment"]
        base_gp = rec_b["goodput_tokens_per_s"]
        emit(metric="chaos_spike_baseline", value=round(base_gp, 3),
             unit="tokens/tick", vs_baseline=None,
             slo_attainment=base_att,
             goodput_tokens_per_s=round(base_gp, 3),
             p50_latency_ticks=p50_b, p99_latency_ticks=p99_b,
             shed=shed_b,
             deadline_exceeded=rec_b["deadline_exceeded"],
             note=f"seeded 2-wave spike, NO controller: every wave-2 "
                  f"request is admitted and burns capacity on tokens "
                  f"that miss the {deadline:.0f}-tick deadline; tick "
                  f"clock (1 tick = 1 fleet step), deterministic")
        emit(**rec_b)

        _Tick.t = 0.0
        rng.seed(0)
        fl_ctrl, _ = build_fleet()
        ctrl = SloController(
            fl_ctrl,
            AutoscaleConfig(target_attainment=0.9,
                            min_queue=2 * slots,  # = the fleet's slot
                            # capacity: shed what cannot make its
                            # deadline, never starve a slot
                            cooldown_ticks=1, relax_after_ticks=8,
                            max_actions_per_episode=6),
            clock=clock)
        _, shed_c, p50_c, p99_c = drive(fl_ctrl, controller=ctrl)
        fl_ctrl.close()
        rec_c = fl_ctrl.record()
        ctrl_att = rec_c["slo_attainment"]
        ctrl_gp = rec_c["goodput_tokens_per_s"]
        emit(metric="chaos_spike_controller", value=round(ctrl_gp, 3),
             unit="tokens/tick",
             vs_baseline=(round(ctrl_gp / base_gp, 3)
                          if base_gp else None),
             slo_attainment=ctrl_att,
             goodput_tokens_per_s=round(ctrl_gp, 3),
             p50_latency_ticks=p50_c, p99_latency_ticks=p99_c,
             shed=shed_c,
             deadline_exceeded=rec_c["deadline_exceeded"],
             actions=ctrl.log.actions_total,
             episodes=ctrl.log.episodes,
             note=f"same seeded spike under SloController: admission "
                  f"tightened after wave 1, wave 2 sheds "
                  f"({shed_c - shed_b:+d} sheds vs baseline) instead "
                  f"of missing deadlines; attainment "
                  f"{base_att:.3f} -> {ctrl_att:.3f}, goodput per "
                  f"tick x{ctrl_gp / max(base_gp, 1e-9):.2f}, "
                  f"vs_baseline is the goodput ratio")
        emit(**ctrl.record())
        emit(**rec_c)

        # -- (2) seeded replica death: fleet MTTR ---------------------
        _Tick.t = 0.0
        rng.seed(0)
        fl_d, reps_d = build_fleet(inject_death=True)
        rids = [fl_d.submit(prompt(), max_new_tokens=new_tokens)
                for _ in range(4 * slots)]
        for _ in range(6):
            fl_d.step()
            _Tick.t += 1.0
        reps_d[0].arm(raise_on_step=(0, None))   # dies next step
        while fl_d.live():
            fl_d.step()
            _Tick.t += 1.0
        fl_d.close()
        mttr = fl_d.mttr()
        rec_d = fl_d.record()
        emit(metric="chaos_mttr_fleet2",
             value=(round(mttr["last"], 3)
                    if mttr["last"] is not None else None),
             unit="ticks", vs_baseline=None,
             mttr_s=mttr["last"], mttr_count=mttr["count"],
             failovers=rec_d["failovers"],
             note=f"replica 0 armed to die mid-run (seeded fault "
                  f"harness): MTTR = failover to first post-recovery "
                  f"progress on the survivors, in ticks "
                  f"(deterministic); all {len(rids)} requests still "
                  f"complete")
        emit(**rec_d)

        # -- (3) planned preemption: emergency snapshot + resume ------
        import tempfile

        from apex_tpu.data import DataLoader
        from apex_tpu.fleet import (ElasticConfig, ElasticTrainer,
                                    PreemptionGuard, TrainingFaults)

        rng_d = np.random.RandomState(7)
        images = rng_d.randint(0, 256, (64, 4, 4, 3), np.uint8)
        labels = np.arange(64, dtype=np.int32)

        def make_loader():
            # the checkpointable (portable python) stream: the state
            # protocol is what makes the resume bitwise
            return DataLoader(images, labels, batch_size=8,
                              shuffle=True, seed=11, native=False)

        def build_np_step(world):
            # numpy step (chaos_smoke discipline): the controller never
            # looks inside the step, and a trivial one keeps the leg
            # fast — determinism, not throughput, is what's measured
            def step(state, batch):
                imgs, lbls = batch
                g = imgs.mean(axis=(0, 2, 3)).astype(np.float32)
                w = state["w"] - 0.1 * (state["w"] - g)
                loss = float(np.mean((w - g) ** 2)) + 1.0 / world
                return {"w": w}, loss
            return step

        total_steps, state0 = 12, {"w": np.zeros(3, np.float32)}

        def run_one(d, loader, log, *, guard=None, faults=None,
                    resume=False, run_name="preempt"):
            def data_fn(i):
                imgs, lbls, _ = loader.next_batch()
                log.append([int(v) for v in lbls])
                return imgs, lbls
            tr = ElasticTrainer(
                build_np_step, dict(state0), world=4, ckpt_dir=d,
                data=loader, guard=guard, faults=faults,
                resume=resume,
                # restore_checkpoint hands back jnp leaves; the numpy
                # step must keep computing in numpy or the resumed
                # trajectory picks up XLA rounding the undisturbed run
                # never saw
                from_host=lambda tree, w: {
                    k: np.asarray(v) for k, v in tree.items()},
                config=ElasticConfig(checkpoint_every=4, min_world=1),
                run=run_name)
            tr.run(total_steps, data_fn)
            return tr

        with tempfile.TemporaryDirectory() as d_und, \
                tempfile.TemporaryDirectory() as d_pre:
            und_log: list = []
            und = run_one(d_und, make_loader(), und_log,
                          run_name="preempt_undisturbed")
            und_losses = [loss for _, loss, _ in und.history]

            pre_log: list = []
            guard = PreemptionGuard(grace_s=60.0)
            faults = TrainingFaults(preemption=(6, 7), seed=0)
            pre = run_one(d_pre, make_loader(), pre_log, guard=guard,
                          faults=faults, run_name="preempt_run")
            assert pre.verdict == "preempted", pre.verdict
            preempt_step = pre._step

            # resume: a FRESH loader + trainer restore the emergency
            # snapshot (tree + data cursor) and finish the run
            res = run_one(d_pre, make_loader(), pre_log, resume=True,
                          run_name="preempt_resumed")
            resume_overhead_s = res.resume_overhead_s
            mttr_s = res.first_commit_at - guard.requested_at

            # the determinism pin, asserted BEFORE the line is emitted
            # (an overhead number for a resume that diverged would be
            # a lie): loss trajectory and consumed-sample-index
            # sequence identical to the undisturbed run
            res_losses = [loss for _, loss, _ in
                          pre.history + res.history]
            assert res_losses == und_losses, (
                f"preempt-resume loss trajectory diverged:\n"
                f"{res_losses}\nvs undisturbed\n{und_losses}")
            assert pre_log == und_log, (
                "preempt-resume consumed-sample sequence diverged")

            emit(metric="chaos_preempt_resume",
                 value=round(resume_overhead_s, 6), unit="s",
                 vs_baseline=None,
                 mttr_s=round(mttr_s, 6),
                 resume_overhead_s=round(resume_overhead_s, 6),
                 resumed_step=res.resumed_step,
                 preempt_step=preempt_step,
                 note=f"planned preemption at observed step 6: "
                      f"emergency snapshot at the step boundary "
                      f"(grace 60s), clean 'preempted' exit, fresh "
                      f"trainer resumed at step {res.resumed_step}; "
                      f"loss trajectory and consumed-sample-index "
                      f"sequence asserted identical to an undisturbed "
                      f"run; value = restore overhead (snapshot + "
                      f"data-cursor load), mttr_s = preempt request "
                      f"to first committed post-resume step")
            emit(**pre.record())

    if chaos_flag and not fleet_n:
        run_chaos_bench()
        # --graph-lint (if also passed) already ran and still gates
        return 1 if lint_errors else 0

    def run_profile_bench():
        """Device-timeline bench: everything here is parsed out of the
        Chrome trace jax.profiler writes — measured device time, not
        host differencing.  Warmup (compile) happens OUTSIDE the
        capture window so the trace holds only warm steps; the blocked
        fetch rides INSIDE it so every dispatched kernel lands before
        stop_trace.  Module-filtered to the step's own HLO module so
        the fetch plumbing never attributes as step time."""
        from apex_tpu.observability import timeline
        from apex_tpu.utils import profiler as prof

        iters, warmup = (10, 3) if on_tpu else (3, 1)

        # -- (1) O2 DDP train step, flat vs hierarchical comm ---------
        ici = two_level_ici()
        Bc, image = (32, 96) if on_tpu else (4, 32)
        B = Bc * ndev
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(B, 3, image, image), jnp.float32)
        y = jnp.asarray(rng.randint(0, 10, B), jnp.int32)
        variants = [("flat", {})]
        if ici >= 2:
            variants.append(("hier", {"comm_topology": "hierarchical",
                                      "ici_size": ici}))
        else:
            print(f"bench --profile: {ndev} device(s) admit no "
                  f"2-level split; hierarchical variant skipped",
                  file=sys.stderr)
        for name, ddp_kw in variants:
            model, opt = amp.initialize(
                models.resnet18(num_classes=10),
                optimizers.FusedAdam(1e-3), opt_level="O2",
                verbosity=0)
            ddp = parallel.DistributedDataParallel(model, **ddp_kw)
            params, bn = model.init(jax.random.PRNGKey(0))
            ost = opt.init(params)
            step = make_resnet_step(model, opt, ddp)
            train = sharded(step, donate=False)
            state = (params, bn, ost)
            for _ in range(warmup):
                state, out = train(state, (x, y))
            float(jnp.sum(out))
            att = timeline.capture(
                lambda s: train(s, (x, y)), state, iters=iters,
                modules=("jit_step",))
            comm_visible = round(
                max(att["collective_ms"] - att["overlap_ms"], 0.0), 4)
            emit(**timeline.profile_record(
                att, metric=f"resnet18_o2_ddp_{name}_profile",
                comm_visible_ms=comm_visible, opt_level="O2",
                note=f"resnet18 O2 DDP step ({name} gradient comm), "
                     f"{iters} warm steps captured under "
                     f"jax.profiler; overlap measured from kernel-"
                     f"interval overlap on the device timeline — the "
                     f"trustworthy ROADMAP-item-2 needle"
                     + ("; CPU mesh: virtual devices share one host, "
                        "so the measured overlap reflects thread "
                        "scheduling, not fabric concurrency"
                        if not on_tpu else "")))
            emit(metric=f"profile_ddp_o2_{name}_comm_visible_ms",
                 value=comm_visible, unit="ms", vs_baseline=None,
                 measured_overlap_fraction=att[
                     "measured_overlap_fraction"],
                 device_busy_ms=att["device_busy_ms"],
                 note=f"collective time NOT hidden under compute on "
                      f"the measured device timeline ({name}); the "
                      f"item-2 overlap work must drive this toward 0 "
                      f"while step time holds")

        # -- (2) windowed decode engine: timeline + KV fragmentation --
        from apex_tpu import serving
        cfg = models.GPTConfig(vocab_size=128, block_size=32,
                               n_layer=2, n_head=4, n_embd=32,
                               dropout=0.0)
        gmodel = models.GPT(cfg)
        gparams, _ = gmodel.init(jax.random.PRNGKey(0))
        gparams = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16)
            if a.dtype == jnp.float32 else a, gparams)
        window, slots = 8, 4
        eng = serving.Engine(gmodel, gparams, slots=slots,
                             buf_len=cfg.block_size, window=window)
        # HALF the slots occupied with short prompts: the partially-
        # filled shape whose nonzero kv_waste_bytes the acceptance
        # criteria pin — free slots waste whole rows, live slots waste
        # the capacity beyond their cur_len.  The token budget outlasts
        # the 3 captured+warm windows (24 ticks < 26) so the requests
        # are still LIVE when the ledger is read.
        for _ in range(slots // 2):
            eng.add_request([1, 2, 3, 4], max_new_tokens=26)
        eng.step()                          # warm/compile
        with prof.profile() as cap:
            for _ in range(2):
                eng.step()
        att = timeline.analyze_capture(cap, modules=("_step_k",),
                                       steps=2)
        s = eng.stats()
        emit(**timeline.profile_record(
            att, metric="gpt_tiny_engine_w8_profile",
            window=window,
            kv_cache_bytes=s["kv_cache_bytes"],
            kv_waste_bytes=s["kv_waste_bytes"],
            kv_utilization=round(s["kv_utilization"], 4),
            note=f"windowed decode engine ({slots // 2}/{slots} slots "
                 f"live, window={window}): device timeline of 2 decode "
                 f"windows + the KV fragmentation ledger — "
                 f"kv_waste_bytes is what ROADMAP item 1's paged "
                 f"allocator must drive down"))
        emit(metric="gpt_tiny_engine_w8_kv_waste_bytes",
             value=s["kv_waste_bytes"], unit="bytes",
             vs_baseline=None, window=window,
             kv_cache_bytes=s["kv_cache_bytes"],
             kv_waste_bytes=s["kv_waste_bytes"],
             kv_utilization=round(s["kv_utilization"], 4),
             note=f"allocated-but-unused KV bytes on the half-filled "
                  f"windowed engine (utilization "
                  f"{s['kv_utilization']:.3f}); the fixed-slot "
                  f"baseline the paged allocator is judged against")

    if profile_flag and not fleet_n:
        run_profile_bench()
        # --graph-lint (if also passed) already ran and still gates
        return 1 if lint_errors else 0

    def timed_scan(ddp, step, state, arrays, per_step_shapes, K, iters,
                   warmup, metric=None):
        """Build the make_step trainer and time one optimizer step.

        ``arrays``: flat leaves holding K*B leading elements each;
        ``per_step_shapes``: their per-step shapes (B, ...).  K > 1 runs
        K real optimizer steps on K distinct micro-batches per dispatch —
        amortizing per-dispatch host latency; K == 1 keeps no micro axis
        but routes through the same builder so all configs share
        construction coverage.  The state is donated (make_step's
        default).

        Returns ``(sec_per_step, cost_fields, memory_record)``: the
        step is AOT-compiled ONCE (lower+compile, reused for the timed
        loop) so ``Compiled.memory_analysis()`` describes the exact
        executable that was timed, and the analytic cost model
        (observability.costmodel) prices one optimizer step per device
        — the fields every fresh train-throughput record must carry at
        schema v3 (mfu / achieved_tflops / flops_per_step /
        peak_bytes), plus the full ``kind: memory`` record emitted
        alongside."""
        from apex_tpu.observability import costmodel
        from apex_tpu.observability import compilation as obscomp
        from apex_tpu.observability import memory as obsmem
        train = ddp.make_step(step, mesh=mesh, steps_per_call=K)
        if K == 1:
            batch = tuple(arrays)
        else:
            batch = tuple(a.reshape((K,) + s)
                          for a, s in zip(arrays, per_step_shapes))
        # ONE trace serves everything: the jaxpr for the cost model and
        # the lowering/compile for the timed loop + memory plan (the
        # AOT .trace() API).  The trace+lower+compile phase is timed
        # SEPARATELY (cold_compile_ms, schema v10): compile seconds
        # must never fold into the trended rate, and the ledger delta
        # across the timed loop pins that nothing re-traced mid-
        # measurement (steady_state_retraces == 0 on a healthy line).
        ledger = obscomp.get_ledger()
        t_compile0 = time.perf_counter()
        traced = train.trace(state, batch)
        closed, lowered = traced.jaxpr, traced.lower()
        compiled = lowered.compile()
        cold_compile_ms = (time.perf_counter() - t_compile0) * 1e3
        traces_before = ledger.total_traces()
        dt = timed(compiled, state, batch, iters, warmup) / K
        steady_retraces = ledger.total_traces() - traces_before
        cost = costmodel.jaxpr_cost(closed)
        plan = obsmem.memory_plan(compiled)
        flops_step = cost.flops / K            # per device: shard_map body
        mdtype = cost.dominant_matmul_dtype or "float32"
        fields = {"flops_per_step": flops_step,
                  "peak_bytes": plan["peak_bytes"],
                  "cold_compile_ms": round(cold_compile_ms, 2),
                  "compiles_total": 1,
                  "steady_state_retraces": steady_retraces,
                  **costmodel.mfu(flops_step, dt, base["arch"], mdtype)}
        mem_rec = {"kind": "memory", "metric": metric or "train_step",
                   "source": "compiled", **cost.to_record(), **plan}
        return dt, fields, mem_rec

    def resnet_config(metric, opt_level, arch, batch_per_chip, image,
                      iters, warmup, sync_bn=False, vs=None,
                      steps_per_call=1, channels_last=False, stem="conv7"):
        model = getattr(models, arch)(channels_last=channels_last,
                                      stem=stem)
        if sync_bn:
            model = parallel.convert_syncbn_model(model)
        model, optimizer = amp.initialize(
            model, optimizers.FusedAdam(lr=0.1), opt_level=opt_level,
            verbosity=0)
        ddp = parallel.DistributedDataParallel(model)
        params, bn_state = model.init(jax.random.PRNGKey(0))
        opt_state = optimizer.init(params)
        global_batch = batch_per_chip * ndev
        rng = np.random.RandomState(0)
        K = steps_per_call
        x = jnp.asarray(rng.randn(K * global_batch, 3, image, image),
                        jnp.float32)
        y = jnp.asarray(rng.randint(0, 1000, K * global_batch), jnp.int32)
        step = make_resnet_step(model, optimizer, ddp)
        dt, cost_fields, mem_rec = timed_scan(
            ddp, step, (params, bn_state, opt_state), (x, y),
            ((global_batch,) + x.shape[1:], (global_batch,)),
            K, iters, warmup, metric=metric)
        ips_chip = global_batch / dt / ndev
        emit(**mem_rec)
        emit(metric=metric, value=round(ips_chip, 1),
             unit="images/sec/chip", steps_per_call=K,
             vs_baseline=(round(ips_chip / vs, 3) if vs else None),
             **cost_fields)

    def bert_config(metric, cfg_name, optimizer, batch_per_chip, seqlen,
                    iters, warmup, steps_per_call=1, tiny=False):
        cfg = (models.BertConfig(vocab_size=128, hidden_size=32,
                                 num_hidden_layers=2,
                                 num_attention_heads=4,
                                 intermediate_size=64,
                                 max_position_embeddings=seqlen)
               if tiny else getattr(models, cfg_name)())
        model, optimizer = amp.initialize(
            models.BertForPretraining(cfg), optimizer, opt_level="O2",
            verbosity=0)
        ddp = parallel.DistributedDataParallel(model)
        params, _ = model.init(jax.random.PRNGKey(0))
        opt_state = optimizer.init(params)
        B = batch_per_chip * ndev
        K = steps_per_call
        rng = np.random.RandomState(0)
        ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (K * B, seqlen)),
                          jnp.int32)
        mlm = jnp.asarray(
            np.where(rng.rand(K * B, seqlen) < 0.15,
                     rng.randint(0, cfg.vocab_size, (K * B, seqlen)), -100),
            jnp.int32)
        nsp = jnp.asarray(rng.randint(0, 2, (K * B,)), jnp.int32)

        def step(state, batch):
            params, opt_state = state
            ids_b, mlm_b, nsp_b = batch

            def loss_fn(p):
                return model.loss(p, ids_b, mlm_b, nsp_b), ()

            loss, _, grads = amp.scaled_grad(loss_fn, params, opt_state,
                                             has_aux=True)
            grads = ddp.allreduce_grads(grads)
            params, opt_state, _ = optimizer.step(params, opt_state, grads)
            return (params, opt_state), lax.pmean(loss, "data")

        dt, cost_fields, mem_rec = timed_scan(
            ddp, step, (params, opt_state), (ids, mlm, nsp),
            ((B, seqlen), (B, seqlen), (B,)), K, iters, warmup,
            metric=metric)
        emit(**mem_rec)
        emit(metric=metric, value=round(B / dt / ndev, 1),
             unit="sequences/sec/chip", steps_per_call=K,
             vs_baseline=None, **cost_fields)

    def gpt_config(metric, cfg, batch_per_chip, seqlen, iters, warmup,
                   steps_per_call=1, model_cls=None):
        model, optimizer = amp.initialize(
            (model_cls or models.GPT)(cfg), optimizers.FusedAdam(lr=1e-4),
            opt_level="O2", verbosity=0)
        ddp = parallel.DistributedDataParallel(model)
        params, _ = model.init(jax.random.PRNGKey(0))
        opt_state = optimizer.init(params)
        B = batch_per_chip * ndev
        K = steps_per_call
        rng = np.random.RandomState(0)
        ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (K * B, seqlen)),
                          jnp.int32)

        def step(state, batch):
            params, opt_state = state
            (ids_b,) = batch

            def loss_fn(p):
                return model.loss(p, ids_b), ()

            loss, _, grads = amp.scaled_grad(loss_fn, params, opt_state,
                                             has_aux=True)
            grads = ddp.allreduce_grads(grads)
            params, opt_state, _ = optimizer.step(params, opt_state,
                                                  grads)
            return (params, opt_state), lax.pmean(loss, "data")

        dt, cost_fields, mem_rec = timed_scan(
            ddp, step, (params, opt_state), (ids,),
            ((B, seqlen),), K, iters, warmup, metric=metric)
        emit(**mem_rec)
        emit(metric=metric, value=round(B / dt / ndev, 1),
             unit="sequences/sec/chip", steps_per_call=K,
             vs_baseline=None, **cost_fields)

    def gpt_decode_config(metric, cfg, batch, prompt, new_tokens,
                          int8_weights=False, int8_cache=False,
                          model_cls=None):
        """KV-cached generation throughput (tokens/sec/chip) — the
        serving path: static cache buffers, one compiled program.
        ``int8_weights``: weight-only int8 (quantization module) — the
        HBM-bandwidth lever for the memory-bound decode loop."""
        model = (model_cls or models.GPT)(cfg)
        params, _ = model.init(jax.random.PRNGKey(0))
        params = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16)
            if x.dtype == jnp.float32 else x, params)
        if int8_weights:
            from apex_tpu import quantization
            params = quantization.quantize_for_decode(params)
        rng = np.random.RandomState(0)
        ctx = getattr(cfg, "block_size", None) \
            or cfg.max_position_embeddings
        buf = np.zeros((batch, ctx), np.int32)
        buf[:, :prompt] = rng.randint(0, cfg.vocab_size, (batch, prompt))
        ids = jnp.asarray(buf)

        cache_dtype = jnp.int8 if int8_cache else None

        def runner(n):
            g = jax.jit(lambda p, b: model.generate_cached(
                p, b, prompt, n, cache_dtype=cache_dtype))
            # timed()'s (state, batch) -> (state, out) shape, reusing its
            # hard-D2H-barrier discipline
            return lambda s, b: (s, g(params, b)[0])

        # the loop also walks the prompt (prefill steps, head skipped),
        # so time a prefill-only run and subtract — the metric is pure
        # decode throughput, invariant to the prompt/new-tokens ratio
        dt_full = timed(runner(new_tokens), None, ids, 3, 1)
        dt_prefill = timed(runner(0), None, ids, 3, 1)
        if prompt > 0 and dt_prefill > 0:
            # time-to-first-token half of the serving story: with
            # chunked prefill this is one MXU pass over the buffer
            emit(metric=f"{metric}_prefill",
                 value=round(batch * prompt / dt_prefill, 1),
                 unit="prompt tokens/sec/chip", vs_baseline=None,
                 note=f"chunked KV-cache prefill, B={batch}, "
                      f"prompt={prompt}")
        if dt_full > dt_prefill * 1.05:
            dt = dt_full - dt_prefill
            how = "prefill time subtracted"
        else:
            # toy/CPU scale: the subtraction sits below run-to-run
            # noise and would fabricate a huge number — report the
            # honest total-time figure instead
            dt = dt_full
            how = "prefill below noise floor; total-time metric"
        emit(metric=metric, value=round(batch * new_tokens / dt, 1),
             unit="tokens/sec/chip", vs_baseline=None,
             note=f"KV-cached greedy decode, B={batch}, prompt={prompt}, "
                  f"{new_tokens} new tokens, "
                  f"{'int8 weights' if int8_weights else 'bf16 params'}+"
                  f"{'int8' if int8_cache else 'bf16'} cache; {how}")

    def t5_config(metric, cfg, batch_per_chip, src_len, tgt_len,
                  iters, warmup):
        """Encoder-decoder training throughput (teacher-forced loss)."""
        model, optimizer = amp.initialize(
            models.T5(cfg), optimizers.FusedAdam(lr=1e-4),
            opt_level="O2", verbosity=0)
        ddp = parallel.DistributedDataParallel(model)
        params, _ = model.init(jax.random.PRNGKey(0))
        opt_state = optimizer.init(params)
        B = batch_per_chip * ndev
        rng = np.random.RandomState(0)
        src = jnp.asarray(rng.randint(2, cfg.vocab_size, (B, src_len)),
                          jnp.int32)
        tgt = jnp.asarray(rng.randint(2, cfg.vocab_size, (B, tgt_len)),
                          jnp.int32)

        def step(state, batch):
            params, opt_state = state
            src_b, tgt_b = batch

            def loss_fn(p):
                return model.loss(p, src_b, tgt_b), ()

            loss, _, grads = amp.scaled_grad(loss_fn, params, opt_state,
                                             has_aux=True)
            grads = ddp.allreduce_grads(grads)
            params, opt_state, _ = optimizer.step(params, opt_state,
                                                  grads)
            return (params, opt_state), lax.pmean(loss, "data")

        dt, cost_fields, mem_rec = timed_scan(
            ddp, step, (params, opt_state), (src, tgt),
            ((B, src_len), (B, tgt_len)), 1, iters, warmup,
            metric=metric)
        emit(**mem_rec)
        emit(metric=metric, value=round(B / dt / ndev, 1),
             unit="sequences/sec/chip", vs_baseline=None, **cost_fields)

    def engine_config(metric, cfg, slots, prompt, new_tokens,
                      model_cls=None, rolling=False, window=1,
                      paged=False, block_size=8, num_blocks=None):
        """Continuous-batching engine throughput: keep every slot busy
        (re-admit a fresh request the moment one finishes) and measure
        steady-state generated TOKENS (not step() calls — a windowed
        step emits up to ``window`` per slot) per second.  ``window=1``
        pays the per-token host sync; ``window=K`` fetches once per K
        in-graph ticks, so the w1-vs-wK line pair is the decode-window
        speedup measured on the same shapes.  ``paged=True`` serves
        the same shapes through the PagedEngine's block pool instead
        of fixed rows (admission_mode says which on every line)."""
        from apex_tpu import serving
        from apex_tpu.observability import compilation as obscomp
        model = (model_cls or models.GPT)(cfg)
        params, _ = model.init(jax.random.PRNGKey(0))
        params = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16)
            if x.dtype == jnp.float32 else x, params)
        ctx = getattr(cfg, "block_size", None) \
            or cfg.max_position_embeddings
        # the compile-plane split (schema v10): everything traced from
        # construction through the warmup steps is the cold cost
        # (ledger-attributed wall seconds), and the timed loop must add
        # ZERO traces — a retrace mid-measurement means the rate below
        # includes a recompile
        ledger = obscomp.get_ledger()
        traces0, wall0 = ledger.total_traces(), ledger.compile_wall_s()
        if paged:
            eng = serving.PagedEngine(model, params, slots=slots,
                                      buf_len=ctx,
                                      block_size=block_size,
                                      num_blocks=num_blocks,
                                      window=window)
        else:
            eng = serving.Engine(model, params, slots=slots,
                                 buf_len=ctx, rolling=rolling,
                                 window=window)
        rng = np.random.RandomState(0)

        def admit():
            p = list(rng.randint(0, cfg.vocab_size, prompt))
            if not eng._can_admit_direct(p, new_tokens):
                return False        # paged pool out of block headroom
            eng.add_request(p, max_new_tokens=new_tokens)
            return True

        for _ in range(slots):
            if not admit():
                break
        for _ in range(5):                      # warmup + compile
            eng.step()
        compiles = ledger.total_traces() - traces0
        cold_ms = (ledger.compile_wall_s() - wall0) * 1e3
        traces_ss = ledger.total_traces()
        t0 = time.perf_counter()
        produced = 0
        steps = max(3 * new_tokens, 30)
        for _ in range(steps):
            produced += sum(len(t) for t in eng.step().values())
            while eng._free:
                if not admit():
                    break
        dt = time.perf_counter() - t0
        s = eng.stats()
        block_kw = ({"block_size": s["block_size"],
                     "blocks_total": s["blocks_total"],
                     "blocks_free": s["blocks_free"],
                     "midwindow_admissions": s["midwindow_admissions"]}
                    if paged else {})
        emit(metric=metric, value=round(produced / dt, 1),
             unit="tokens/sec/chip", vs_baseline=None, window=window,
             admission_mode=s["admission_mode"],
             kv_cache_bytes=s["kv_cache_bytes"],
             kv_waste_bytes=s["kv_waste_bytes"],
             kv_utilization=round(s["kv_utilization"], 4),
             tokens_per_sync=round(s["tokens_per_sync"], 2),
             cold_compile_ms=round(cold_ms, 2),
             compiles_total=compiles,
             steady_state_retraces=ledger.total_traces() - traces_ss,
             **block_kw,
             note=f"continuous batching, {slots} slots, decode window="
                  f"{window} (host syncs 1/{window} per token), prompt="
                  f"{prompt}, {new_tokens} new/request, slot re-admit "
                  f"on finish"
                  + (f", paged pool {s['blocks_total']} blocks x "
                     f"{s['block_size']} positions" if paged else "")
                  + (f", O(window) ring cache W="
                     f"{getattr(cfg, 'sliding_window', None)}"
                     if rolling else ""))

    def seq2seq_engine_config(metric, cfg, slots, src_len, new_tokens,
                              window=1):
        """Encoder-decoder continuous batching throughput (T5):
        slot re-admit on finish, steady-state generated tokens/sec;
        ``window`` as in engine_config."""
        from apex_tpu import serving
        from apex_tpu.observability import compilation as obscomp
        model = models.T5(cfg)
        params, _ = model.init(jax.random.PRNGKey(0))
        params = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16)
            if x.dtype == jnp.float32 else x, params)
        ledger = obscomp.get_ledger()
        traces0, wall0 = ledger.total_traces(), ledger.compile_wall_s()
        eng = serving.Seq2SeqEngine(model, params, slots=slots,
                                    src_len=src_len,
                                    max_new_cap=new_tokens,
                                    window=window)
        rng = np.random.RandomState(0)

        def admit():
            n = int(rng.randint(src_len // 2, src_len + 1))
            eng.add_request(list(rng.randint(2, cfg.vocab_size, n)),
                            max_new_tokens=new_tokens)

        for _ in range(slots):
            admit()
        for _ in range(5):
            eng.step()
        compiles = ledger.total_traces() - traces0
        cold_ms = (ledger.compile_wall_s() - wall0) * 1e3
        traces_ss = ledger.total_traces()
        t0 = time.perf_counter()
        produced = 0
        steps = max(3 * new_tokens, 30)
        for _ in range(steps):
            produced += sum(len(t) for t in eng.step().values())
            while eng._free:
                admit()
        dt = time.perf_counter() - t0
        s = eng.stats()
        emit(metric=metric, value=round(produced / dt, 1),
             unit="tokens/sec/chip", vs_baseline=None, window=window,
             admission_mode=s["admission_mode"],
             kv_cache_bytes=s["kv_cache_bytes"],
             kv_waste_bytes=s["kv_waste_bytes"],
             kv_utilization=round(s["kv_utilization"], 4),
             cold_compile_ms=round(cold_ms, 2),
             compiles_total=compiles,
             steady_state_retraces=ledger.total_traces() - traces_ss,
             note=f"seq2seq continuous batching, {slots} slots, "
                  f"decode window={window}, src<={src_len}, "
                  f"{new_tokens} new/request, encoder pass per "
                  f"admission")

    def prefix_admit_config(metric, cfg, prompt, prefix_len,
                            model_cls=None):
        """Admission latency, full prefill vs prefix-sharing splice:
        the serving lever for shared system prompts.  Measures mean
        admit+free time per request both ways on the same engine
        shapes."""
        from apex_tpu import serving
        model = (model_cls or models.GPT)(cfg)
        params, _ = model.init(jax.random.PRNGKey(0))
        params = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16)
            if x.dtype == jnp.float32 else x, params)
        ctx = getattr(cfg, "block_size", None) \
            or cfg.max_position_embeddings
        rng = np.random.RandomState(0)
        pref = list(rng.randint(0, cfg.vocab_size, prefix_len))

        def run(eng, use_prefix, iters):
            ts = []
            for _ in range(iters):
                p = (pref if use_prefix else list(
                    rng.randint(0, cfg.vocab_size, prefix_len))) \
                    + list(rng.randint(0, cfg.vocab_size,
                                       prompt - prefix_len))
                t0 = time.perf_counter()
                rid = eng.add_request(p, max_new_tokens=1)
                jax.block_until_ready(
                    jax.tree_util.tree_leaves(eng.cache)[0])
                ts.append(time.perf_counter() - t0)
                eng.step()                  # finish + free the slot
            return ts

        eng = serving.Engine(model, params, slots=1, buf_len=ctx,
                             prefix_pool=1)
        eng.register_prefix(pref)
        run(eng, False, 3)                  # compile both paths
        run(eng, True, 3)
        full = run(eng, False, 10)
        spliced = run(eng, True, 10)
        f_ms = float(np.mean(full)) * 1e3
        s_ms = float(np.mean(spliced)) * 1e3
        emit(metric=metric, value=round(f_ms / s_ms, 2),
             unit="admit_speedup_x", vs_baseline=None,
             note=f"prefix-sharing splice: admit {s_ms:.1f} ms vs full "
                  f"prefill {f_ms:.1f} ms (prompt={prompt}, shared "
                  f"prefix={prefix_len}, buf={ctx})")

    def allreduce_bw():
        n = 25_000_000 if on_tpu else 1_000_000
        buf = jnp.ones((n,), jnp.float32)

        def step(state, batch):
            g = {"g": state[0] + batch[0][0, 0]}
            out = parallel.allreduce_grads_tree(g, "data")
            return (out["g"],), jnp.sum(out["g"][:8])

        train = sharded(step)
        dt = timed(train, (buf,), (jnp.ones((ndev, 1)),
                                   jnp.zeros((ndev, 1))), 10, 2)
        emit(metric="ddp_allreduce_bandwidth", value=round(n * 4 / dt / 1e9,
                                                           2),
             unit="GB/s/chip", vs_baseline=None,
             note="chunked-psum path; bytes of one replica's buffer / step "
                  "time")

    def optimizer_step_time():
        n = 25_557_032 if on_tpu else 1_000_000   # resnet50 param count
        opt = optimizers.FusedAdam(lr=1e-3)
        flat = jnp.zeros((n,), jnp.float32)
        state = opt.init(flat)
        g = jnp.ones((n,), jnp.float32)

        def step(s, batch):
            p, st = s
            p, st = opt.update(g, st, p)
            return (p, st), jnp.sum(p[:8])

        train = jax.jit(step)
        dt = timed(train, (flat, state), None, 20, 3)
        emit(metric="fused_adam_step_time", value=round(dt * 1e3, 3),
             unit="ms", vs_baseline=None,
             note=f"{n} params, flat fp32 buffer")

        # LAMB on a BERT-large-shaped ragged tree (per-tensor trust ratios)
        rng = np.random.RandomState(0)
        nleaves = 393 if on_tpu else 64
        scale_elems = (850_000 if on_tpu else 1_000)
        tree = {f"p{i}": jnp.asarray(
            rng.randn(rng.randint(scale_elems // 2, scale_elems)),
            jnp.float32) for i in range(nleaves)}
        lamb = optimizers.FusedLAMB(lr=1e-3)
        lstate = lamb.init(tree)
        gtree = jax.tree_util.tree_map(jnp.ones_like, tree)

        def lstep(s, batch):
            p, st = s
            p, st = lamb.update(gtree, st, p)
            return (p, st), jnp.sum(p["p0"][:8])

        ltrain = jax.jit(lstep)
        dt = timed(ltrain, (tree, lstate), None, 10, 2)
        total = sum(int(l.size) for l in jax.tree_util.tree_leaves(tree))
        emit(metric="fused_lamb_step_time", value=round(dt * 1e3, 3),
             unit="ms", vs_baseline=None,
             note=f"{nleaves}-leaf tree, {total} params, per-tensor "
                  "trust ratios via segment map")

    # -- run the suite ------------------------------------------------------
    if on_tpu:
        jobs = [
            ("resnet50_amp_o2_ddp_train_throughput",
             lambda: resnet_config("resnet50_amp_o2_ddp_train_throughput",
                                   "O2", "resnet50", 128, 224, 20, 3,
                                   vs=BASELINE_IMG_PER_SEC_PER_CHIP)),
            ("resnet50_o0_fp32_train_throughput",
             lambda: resnet_config("resnet50_o0_fp32_train_throughput",
                                   "O0", "resnet50", 64, 224, 10, 2)),
            ("resnet50_o2_syncbn_train_throughput",
             lambda: resnet_config("resnet50_o2_syncbn_train_throughput",
                                   "O2", "resnet50", 128, 224, 10, 2,
                                   sync_bn=True)),
            ("bert_base_o2_fused_adam_train_throughput",
             lambda: bert_config("bert_base_o2_fused_adam_train_throughput",
                                 "bert_base", optimizers.FusedAdam(lr=1e-4),
                                 32, 128, 10, 2)),
            ("bert_large_o2_fused_lamb_train_throughput",
             lambda: bert_config(
                 "bert_large_o2_fused_lamb_train_throughput", "bert_large",
                 optimizers.FusedLAMB(lr=1e-3), 8, 128, 8, 2)),
            ("bert_base_o2_scan4_train_throughput",
             lambda: bert_config(
                 "bert_base_o2_scan4_train_throughput", "bert_base",
                 optimizers.FusedAdam(lr=1e-4), 32, 128, 4, 1,
                 steps_per_call=4)),
            ("gpt2_small_o2_causal_flash_train_throughput",
             lambda: gpt_config(
                 "gpt2_small_o2_causal_flash_train_throughput",
                 models.GPTConfig(n_layer=12, n_head=12, n_embd=768,
                                  vocab_size=50257, block_size=512,
                                  dropout=0.0),
                 8, 512, 8, 2)),
            ("gpt2_small_decode_throughput",
             lambda: gpt_decode_config(
                 "gpt2_small_decode_throughput",
                 models.GPTConfig(n_layer=12, n_head=12, n_embd=768,
                                  vocab_size=50257, block_size=512,
                                  dropout=0.0),
                 8, 64, 128)),
            ("gpt2_small_decode_int8_throughput",
             lambda: gpt_decode_config(
                 "gpt2_small_decode_int8_throughput",
                 models.GPTConfig(n_layer=12, n_head=12, n_embd=768,
                                  vocab_size=50257, block_size=512,
                                  dropout=0.0),
                 8, 64, 128, int8_weights=True, int8_cache=True)),
            # long-context single-chip: the blocked flash path at 8x the
            # training context (tests/test_flash_long.py compiles
            # T=32768 on the chip; this records sustained training
            # throughput at a long-but-benchable length)
            ("gpt2_small_o2_flash_t4096_train_throughput",
             lambda: gpt_config(
                 "gpt2_small_o2_flash_t4096_train_throughput",
                 models.GPTConfig(n_layer=12, n_head=12, n_embd=768,
                                  vocab_size=50257, block_size=4096,
                                  dropout=0.0),
                 1, 4096, 6, 2)),
            # same config under per-block remat ("dots"): records what
            # the long-context HBM lever costs in recompute throughput
            # (the lever's value is the larger batch/length it unlocks)
            ("gpt2_small_o2_flash_t4096_remat_train_throughput",
             lambda: gpt_config(
                 "gpt2_small_o2_flash_t4096_remat_train_throughput",
                 models.GPTConfig(n_layer=12, n_head=12, n_embd=768,
                                  vocab_size=50257, block_size=4096,
                                  dropout=0.0, remat="dots"),
                 1, 4096, 6, 2)),
            # Llama family: GQA (4 kv-heads) at GPT-2-small scale —
            # records the RMSNorm/RoPE/SwiGLU train path and the
            # compact-GQA-cache decode path on hardware
            ("llama_gqa_o2_train_throughput",
             lambda: gpt_config(
                 "llama_gqa_o2_train_throughput",
                 models.LlamaConfig(
                     vocab_size=32000, hidden_size=768,
                     intermediate_size=2048, num_hidden_layers=12,
                     num_attention_heads=12, num_key_value_heads=4,
                     max_position_embeddings=512,
                     tie_word_embeddings=True),
                 8, 512, 8, 2, model_cls=models.Llama)),
            ("llama_gqa_decode_throughput",
             lambda: gpt_decode_config(
                 "llama_gqa_decode_throughput",
                 models.LlamaConfig(
                     vocab_size=32000, hidden_size=768,
                     intermediate_size=2048, num_hidden_layers=12,
                     num_attention_heads=12, num_key_value_heads=4,
                     max_position_embeddings=512,
                     tie_word_embeddings=True),
                 8, 64, 128, model_cls=models.Llama)),
            ("t5_small_o2_train_throughput",
             lambda: t5_config(
                 "t5_small_o2_train_throughput",
                 models.T5Config(vocab_size=32128, d_model=512,
                                 d_kv=64, d_ff=2048, num_layers=6,
                                 num_heads=8, dropout_rate=0.0),
                 8, 256, 64, 8, 2)),
            ("gpt2_small_engine_decode_throughput",
             lambda: engine_config(
                 "gpt2_small_engine_decode_throughput",
                 models.GPTConfig(n_layer=12, n_head=12, n_embd=768,
                                  vocab_size=50257, block_size=512,
                                  dropout=0.0),
                 8, 64, 64)),
            # same shapes, decode window 8: the w1/w8 pair measures
            # what the once-per-window host fetch buys on hardware
            ("gpt2_small_engine_decode_w8_throughput",
             lambda: engine_config(
                 "gpt2_small_engine_decode_w8_throughput",
                 models.GPTConfig(n_layer=12, n_head=12, n_embd=768,
                                  vocab_size=50257, block_size=512,
                                  dropout=0.0),
                 8, 64, 64, window=8)),
            # paged twin of the w8 line: same shapes through the
            # block-pool allocator — the fixed/paged pair on hardware
            # is the fragmentation win at production sizes
            ("gpt2_small_engine_decode_paged_w8_throughput",
             lambda: engine_config(
                 "gpt2_small_engine_decode_paged_w8_throughput",
                 models.GPTConfig(n_layer=12, n_head=12, n_embd=768,
                                  vocab_size=50257, block_size=512,
                                  dropout=0.0),
                 8, 64, 64, window=8, paged=True, block_size=64)),
            ("t5_small_seq2seq_engine_decode_throughput",
             lambda: seq2seq_engine_config(
                 "t5_small_seq2seq_engine_decode_throughput",
                 models.T5Config(vocab_size=32128, d_model=512,
                                 d_kv=64, d_ff=2048, num_layers=6,
                                 num_heads=8, dropout_rate=0.0),
                 8, 128, 64)),
            ("mistral_rolling_engine_decode_throughput",
             lambda: engine_config(
                 "mistral_rolling_engine_decode_throughput",
                 models.LlamaConfig(
                     vocab_size=32000, hidden_size=768,
                     intermediate_size=2048, num_hidden_layers=8,
                     num_attention_heads=12, num_key_value_heads=4,
                     max_position_embeddings=4096, sliding_window=1024,
                     tie_word_embeddings=True),
                 8, 512, 64, model_cls=models.Llama, rolling=True)),
            ("gpt2_small_engine_prefix_admit_speedup",
             lambda: prefix_admit_config(
                 "gpt2_small_engine_prefix_admit_speedup",
                 models.GPTConfig(n_layer=12, n_head=12, n_embd=768,
                                  vocab_size=50257, block_size=512,
                                  dropout=0.0),
                 448, 384)),
            # Mixtral family: top-2 SwiGLU MoE (8 experts) on the Llama
            # backbone — single-chip all experts run locally; the
            # number records MoE dispatch overhead vs the dense path
            ("mixtral_8e_top2_o2_train_throughput",
             lambda: gpt_config(
                 "mixtral_8e_top2_o2_train_throughput",
                 models.MixtralConfig(
                     vocab_size=32000, hidden_size=768,
                     intermediate_size=2048, num_hidden_layers=8,
                     num_attention_heads=12, num_key_value_heads=4,
                     max_position_embeddings=512,
                     tie_word_embeddings=True, num_local_experts=8,
                     num_experts_per_tok=2),
                 4, 512, 6, 2, model_cls=models.Mixtral)),
            ("ddp_allreduce_bandwidth", allreduce_bw),
            ("optimizer_step_time", optimizer_step_time),
            ("resnet50_amp_o2_ddp_nhwc_train_throughput",
             lambda: resnet_config(
                 "resnet50_amp_o2_ddp_nhwc_train_throughput",
                 "O2", "resnet50", 128, 224, 10, 2,
                 vs=BASELINE_IMG_PER_SEC_PER_CHIP, channels_last=True)),
            ("resnet50_amp_o2_ddp_scan4_train_throughput",
             lambda: resnet_config(
                 "resnet50_amp_o2_ddp_scan4_train_throughput",
                 "O2", "resnet50", 128, 224, 5, 1,
                 vs=BASELINE_IMG_PER_SEC_PER_CHIP, steps_per_call=4)),
            ("resnet50_amp_o2_ddp_s2d_train_throughput",
             lambda: resnet_config(
                 "resnet50_amp_o2_ddp_s2d_train_throughput",
                 "O2", "resnet50", 128, 224, 20, 3,
                 vs=BASELINE_IMG_PER_SEC_PER_CHIP,
                 stem="space_to_depth")),
        ]
    else:  # smoke sizes so the harness runs anywhere
        jobs = [
            ("resnet18_o0_fp32_train_throughput",
             lambda: resnet_config("resnet18_o0_fp32_train_throughput",
                                   "O0", "resnet18", 4, 32, 2, 1)),
            ("bert_tiny_o2_scan2_train_throughput",
             lambda: bert_config(
                 "bert_tiny_o2_scan2_train_throughput", "bert_base",
                 optimizers.FusedAdam(lr=1e-4), 2, 16, 2, 1,
                 steps_per_call=2, tiny=True)),
            ("gpt_tiny_o2_train_throughput",
             lambda: gpt_config(
                 "gpt_tiny_o2_train_throughput",
                 models.GPTConfig(vocab_size=128, block_size=16,
                                  n_layer=2, n_head=4, n_embd=32,
                                  dropout=0.0),
                 2, 16, 2, 1)),
            ("gpt_tiny_decode_throughput",
             lambda: gpt_decode_config(
                 "gpt_tiny_decode_throughput",
                 models.GPTConfig(vocab_size=128, block_size=16,
                                  n_layer=2, n_head=4, n_embd=32,
                                  dropout=0.0),
                 2, 4, 8)),
            ("t5_tiny_o2_train_throughput",
             lambda: t5_config(
                 "t5_tiny_o2_train_throughput",
                 models.T5Config(vocab_size=128, d_model=32, d_kv=8,
                                 d_ff=64, num_layers=1, num_heads=4,
                                 dropout_rate=0.0,
                                 relative_attention_num_buckets=8,
                                 relative_attention_max_distance=16),
                 2, 12, 6, 2, 1)),
            ("gpt_tiny_engine_decode_throughput",
             lambda: engine_config(
                 "gpt_tiny_engine_decode_throughput",
                 models.GPTConfig(vocab_size=128, block_size=16,
                                  n_layer=2, n_head=4, n_embd=32,
                                  dropout=0.0),
                 2, 4, 6)),
            # decode-window pair: identical shapes, window 1 vs 8, and
            # new_tokens a window multiple so wK runs full windows —
            # the w1/w8 ratio is the pure host-sync amortization win
            ("gpt_tiny_engine_decode_w1_throughput",
             lambda: engine_config(
                 "gpt_tiny_engine_decode_w1_throughput",
                 models.GPTConfig(vocab_size=128, block_size=16,
                                  n_layer=2, n_head=4, n_embd=32,
                                  dropout=0.0),
                 2, 4, 8, window=1)),
            ("gpt_tiny_engine_decode_w8_throughput",
             lambda: engine_config(
                 "gpt_tiny_engine_decode_w8_throughput",
                 models.GPTConfig(vocab_size=128, block_size=16,
                                  n_layer=2, n_head=4, n_embd=32,
                                  dropout=0.0),
                 2, 4, 8, window=8)),
            # paged twin of the w8 line: block-pool allocator on the
            # same shapes, smoke-sized (fixed/paged fragmentation pair)
            ("gpt_tiny_engine_decode_paged_w8_throughput",
             lambda: engine_config(
                 "gpt_tiny_engine_decode_paged_w8_throughput",
                 models.GPTConfig(vocab_size=128, block_size=16,
                                  n_layer=2, n_head=4, n_embd=32,
                                  dropout=0.0),
                 2, 4, 8, window=8, paged=True, block_size=4)),
            ("t5_tiny_seq2seq_engine_decode_throughput",
             lambda: seq2seq_engine_config(
                 "t5_tiny_seq2seq_engine_decode_throughput",
                 models.T5Config(vocab_size=64, d_model=32, d_kv=8,
                                 d_ff=64, num_layers=2, num_heads=4,
                                 dropout_rate=0.0,
                                 relative_attention_num_buckets=8,
                                 relative_attention_max_distance=16),
                 2, 8, 6)),
            ("llama_tiny_rolling_engine_decode_throughput",
             lambda: engine_config(
                 "llama_tiny_rolling_engine_decode_throughput",
                 models.LlamaConfig(
                     vocab_size=128, hidden_size=32,
                     intermediate_size=64, num_hidden_layers=2,
                     num_attention_heads=4, num_key_value_heads=2,
                     max_position_embeddings=16, sliding_window=6,
                     tie_word_embeddings=True),
                 2, 4, 6, model_cls=models.Llama, rolling=True)),
            ("gpt_tiny_engine_prefix_admit_speedup",
             lambda: prefix_admit_config(
                 "gpt_tiny_engine_prefix_admit_speedup",
                 models.GPTConfig(vocab_size=128, block_size=16,
                                  n_layer=2, n_head=4, n_embd=32,
                                  dropout=0.0),
                 12, 8)),
            ("mixtral_tiny_o2_train_throughput",
             lambda: gpt_config(
                 "mixtral_tiny_o2_train_throughput",
                 models.MixtralConfig(
                     vocab_size=128, hidden_size=32,
                     intermediate_size=64, num_hidden_layers=2,
                     num_attention_heads=4, num_key_value_heads=2,
                     max_position_embeddings=16,
                     tie_word_embeddings=True, num_local_experts=4,
                     num_experts_per_tok=2),
                 2, 16, 2, 1, model_cls=models.Mixtral)),
            ("llama_tiny_gqa_decode_throughput",
             lambda: gpt_decode_config(
                 "llama_tiny_gqa_decode_throughput",
                 models.LlamaConfig(
                     vocab_size=128, hidden_size=32,
                     intermediate_size=64, num_hidden_layers=2,
                     num_attention_heads=4, num_key_value_heads=2,
                     max_position_embeddings=16,
                     tie_word_embeddings=True),
                 2, 4, 8, model_cls=models.Llama)),
            ("ddp_allreduce_bandwidth", allreduce_bw),
            ("optimizer_step_time", optimizer_step_time),
            ("resnet18_amp_o2_ddp_scan2_train_throughput",
             lambda: resnet_config(
                 "resnet18_amp_o2_ddp_scan2_train_throughput",
                 "O2", "resnet18", 8, 32, 2, 1,
                 vs=BASELINE_IMG_PER_SEC_PER_CHIP, steps_per_call=2)),
            ("resnet18_amp_o2_ddp_train_throughput",
             lambda: resnet_config("resnet18_amp_o2_ddp_train_throughput",
                                   "O2", "resnet18", 8, 32, 3, 1,
                                   vs=BASELINE_IMG_PER_SEC_PER_CHIP)),
        ]

    # APEX_BENCH_ONLY=metric1,metric2 filters the job list (one chip
    # call is bounded; run what the call is for)
    only = os.environ.get("APEX_BENCH_ONLY")
    if only:
        want = {s.strip() for s in only.split(",") if s.strip()}
        jobs = [(n, j) for n, j in jobs if n in want]
        missing = want - {n for n, _ in jobs}
        if missing:
            print(f"bench: APEX_BENCH_ONLY names unknown configs "
                  f"{sorted(missing)}", file=sys.stderr)
        if not jobs:
            # fail loudly: a silently-empty filter captures nothing
            raise SystemExit(
                f"bench: APEX_BENCH_ONLY={only!r} matched no configs "
                f"on this backend (on_tpu={on_tpu})")

    # one config's exception must not hide the configs after it: it
    # leaves an error line in the stream, its traceback on stderr, and
    # a non-zero exit status
    failed = []
    for name, job in jobs:
        try:
            job()
        except Exception:       # noqa: BLE001 — boundary: report, go on
            failed.append(name)
            err = traceback.format_exc()
            print(err, file=sys.stderr)
            emit(metric=name, value=None, unit=None, vs_baseline=None,
                 error=err.strip().splitlines()[-1])
    if failed:
        print(f"bench: {len(failed)} config(s) failed: {failed}",
              file=sys.stderr)

    # --graph-lint findings and failed configs both surface in the exit
    # status (the measurements above still ran and were emitted)
    return 1 if (lint_errors or failed) else 0


if __name__ == "__main__":
    sys.exit(main())
