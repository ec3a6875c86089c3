#!/usr/bin/env python
"""CI gate: machine-check the BENCH_r*.json trajectory.

The per-round bench artifacts wrap a JSONL ``tail`` of schema-versioned
records (tests/ci/check_bench_schema.py validates each record's shape;
THIS gate validates the trend ACROSS rounds).  Failure classes:

1. **Fresh regression.**  Consecutive FRESH measurements of the same
   (metric, backend) that got worse by more than ``--tol`` (default
   25%): error on accelerator backends.  CPU-smoke lines live on a
   shared noisy container where run-to-run swings of several x are
   routine (fused_lamb_step_time moved 4.7x between r03 and r04 with
   no code change on that path), so CPU regressions are REPORTED as
   warnings but do not gate — the byte/plan fields and the tier-1
   suite are the portable CPU signals, hardware lines are the timing
   signal.  ``--strict-cpu`` promotes them to errors.
2. **Comm-overlap regression** (schema v9 overlap fields).  Fresh
   metric lines carrying ``overlap_fraction`` /
   ``measured_overlap_fraction`` (step-time attribution and profile
   lines from ``bench.py --comm`` / ``--profile``) trend per
   (metric, backend, field): a fraction that DROPS past ``--tol``
   after the overlap work drove it off zero is comm sliding back onto
   the critical path — error on accelerator backends, warning on CPU
   smoke (virtual devices share one host; measured overlap there
   reflects thread scheduling).  A ``comm_visible_ms`` field that
   GROWS past ``--tol`` follows the same policy.  A zero baseline cuts
   both ways: a FRACTION at 0 (the reduce-after-backward world) never
   trends — there is no overlap to lose yet — but a
   ``comm_visible_ms`` of 0 is the success state, and comm returning
   from fully hidden to measurably visible gates as the worst
   regression the column exists for.
3. **Peak-memory / MFU regression** (schema v3 cost-model fields).
   ``peak_bytes`` — on train-throughput lines and ``kind: memory``
   records — is a property of the COMPILED executable, deterministic
   on any backend, so growth past ``--mem-tol`` (default 25%) gates
   even on CPU: a step that suddenly plans 30% more device memory
   regressed no matter how noisy the host clock is (ROADMAP item 4's
   "pin peak-memory in bench").  ``mfu`` is timing-derived, so its
   regressions follow the same accelerator-gates / CPU-warns policy
   as throughput.  Stale replays are partitioned out of both trends
   exactly like throughput lines.

4. **Compile-plane regression** (schema v10 compile fields).  A fresh
   line carrying ``steady_state_retraces`` > 0 is an ERROR on every
   backend: the compilation ledger saw a jit re-trace DURING the timed
   loop, so the trended rate includes a recompile — that is a
   deterministic contract violation, not timing noise (the zero-
   retrace steady state is tier-1-pinned; a bench line breaking it
   means the measured configuration regressed the contract).
   ``cold_compile_ms`` growth past ``--tol`` follows the
   accelerator-gates / CPU-warns policy like MFU — compile time is
   wall-clock, but a 2x jump on hardware is a real compile-plane
   regression (a new shape family, a cache stopped hitting).

5. **Tenant-plane regression** (schema v11 tenant fields).  Per-tenant
   goodput lines from the ``bench.py --fleet`` two-tenant leg trend
   through the ordinary (metric, backend) path — the tenant is part of
   the metric name — and their ``slo_attainment`` field trends as its
   own column: an attainment drop past ``--tol`` follows the
   accelerator-gates / CPU-warns policy (attainment is timing-derived
   on a noisy host).  The ``*_tenant_parity`` line is NOT timing: the
   leg tags every request, so the sum of per-tenant goodput tokens
   over the fleet total must be 1.0 — a fresh parity off 1.0 by more
   than 1% means the tenant split lost or double-counted tokens, a
   deterministic accounting bug that gates on every backend (the
   steady-state-retrace rule, not the MFU rule).

6. **KV-plane regression** (schema v12 block-pool fields).  Fresh
   engine lines carry the PR 13 fragmentation ledger
   (``kv_waste_bytes``), and the paged allocator exists to drive it
   DOWN — so waste trends as a lower-is-better column per
   (metric, backend): growth past ``--tol`` errors on accelerator
   backends and warns on CPU smoke (the sampled waste depends on
   where in the admit/finish cycle the snapshot lands, which is
   timing on a noisy host).  A zero baseline is the success state —
   waste returning from 0 to measurably nonzero gates like comm
   coming back onto the critical path.  Separately, the v12 FIELD
   contract is deterministic and gates on every backend: a fresh
   ``engine_decode`` line whose round declares ``schema_version``
   >= 12 must carry ``admission_mode``, and a paged line must carry
   ``block_size``/``blocks_total``/``blocks_free`` — archived rounds
   that declare an older version are exempt (they were valid when
   written).

7. **Sharding-plane regression** (schema v13 ``kind: sharding``
   records from ``bench.py --graph-lint`` /
   ``python -m apex_tpu.analysis --sharding``).  The replication
   ledger's ``replicated_bytes`` is derived statically from the traced
   jaxpr — deterministic on every backend, exactly like
   ``peak_bytes`` — so growth past ``--mem-tol`` gates per
   (entry_point, backend) even on CPU smoke: a train step that
   suddenly duplicates more world bytes un-sharded something (a ZeRO
   shard silently re-replicated, an optimizer state that stopped
   partitioning).  Shrinkage is the ROADMAP item 2 direction and never
   gates.  Stale replays are partitioned out like everything else.

8. **QoS-plane regression** (schema v14 fields from the ``bench.py
   --fleet`` QoS leg).  Per-class goodput lines carry ``qos_class`` +
   ``slo_attainment``; attainment trends per (metric, backend) like
   the tenant column (timing-derived: accelerator gates, CPU warns),
   and the ``*_qos_aggregate_goodput`` line's ``vs_baseline`` — the
   QoS-tagged pass over the untagged baseline — dropping below 0.95
   follows the same policy (the WFQ plane is allowed ~5% overhead,
   not more).  The ``*_preemption_parity`` line is NOT timing: a
   preempted-then-readmitted request's tokens must equal an
   undisturbed run token-for-token, so a fresh parity off 1.0 by more
   than 1% is a deterministic exactness violation that gates on every
   backend (the steady-state-retrace rule — and the line's own
   ``steady_state_retraces`` must be 0, enforced by the v10 gate).

Records marked ``stale: true`` are partitioned out of the trend
entirely: a re-emitted record can neither regress nor improve a metric.
Error lines (``value: null`` + ``error``) and
flag/summary records are likewise excluded, as are per-run
``kind: numerics`` gradient-health dumps (schema v4), per-run
``kind: run`` supervisor verdicts (schema v5), per-run
``kind: recovery`` controller snapshots (schema v6), per-capture
``kind: profile`` device-timeline attributions (schema v8) and
per-run ``kind: fleet`` snapshots (whose v11 per-tenant blocks
describe one run's traffic mix, not a cross-round trend) — their
stale replays still count toward the partition tally.  The ``run_supervisor_overhead``
and ``fleet_goodput`` *metric* lines from ``bench.py --run`` are
ordinary measurements and DO trend (accelerator gates, CPU warns).

Usage::

    python tests/ci/check_bench_trend.py                 # repo root
    python tests/ci/check_bench_trend.py --dir /path     # other history
    python tests/ci/check_bench_trend.py --tol 0.4
    python tests/ci/check_bench_trend.py --mem-tol 0.1
    python tests/ci/check_bench_trend.py --strict-cpu

Exit 0 = trend clean (warnings allowed), 1 = any error.  Pure stdlib —
importable from CI without jax.
"""

import argparse
import glob
import json
import os
import sys

_ROOT = os.path.abspath(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), os.pardir, os.pardir))

# units where a LOWER value is better (times); anything else is a
# rate/ratio where higher is better
LOWER_IS_BETTER_UNITS = {"ms", "s", "us", "ns", "seconds"}


def load_rounds(directory):
    """[(round_name, [records])] in round order.  Each BENCH_r*.json is
    the runbook wrapper {n, cmd, rc, tail, parsed}; ``tail`` holds the
    run's last stdout bytes, so its FIRST line may be truncated —
    unparseable lines are skipped, complete JSONL records kept."""
    rounds = []
    for path in sorted(glob.glob(os.path.join(directory,
                                              "BENCH_r*.json"))):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError) as e:
            print(f"trend: cannot read {path}: {e}", file=sys.stderr)
            continue
        recs = []
        for ln in str(doc.get("tail", "")).splitlines():
            ln = ln.strip()
            if not ln.startswith("{"):
                continue            # stderr chatter / '# buffered:'
            try:
                rec = json.loads(ln)
            except ValueError:
                continue            # truncated head of the tail
            if isinstance(rec, dict):
                recs.append(rec)
        rounds.append((os.path.basename(path), recs))
    return rounds


def is_measurement(rec):
    """Fresh-or-stale numeric metric line (what the trend is made of):
    excludes error lines, flags, and non-metric kinds (fleet / trace /
    graph_lint records interleave in the same streams)."""
    if "kind" in rec and rec.get("kind") not in (None, "bench"):
        return False
    v = rec.get("value")
    return (isinstance(rec.get("metric"), str)
            and isinstance(v, (int, float))
            and not isinstance(v, bool)
            and "error" not in rec
            and rec.get("unit") != "flag")


def is_stale(rec):
    return rec.get("stale") is True


def is_cpu(rec):
    # pre-envelope records (r01/r02) carry no backend field; they were
    # fresh measurements on whatever ran — treat unknown as gating
    # (nothing in the real history compares across the unknown key)
    return rec.get("backend") == "cpu"


def direction(rec):
    unit = str(rec.get("unit", ""))
    if unit in LOWER_IS_BETTER_UNITS:
        return "lower"
    return "higher"


def _mem_subject(rec):
    """Trend key for a peak-bytes / mfu carrier: the bench metric or
    the analysis entry point (``kind: memory`` records from
    ``python -m apex_tpu.analysis --memory``)."""
    s = rec.get("metric") or rec.get("entry_point")
    return s if isinstance(s, str) and s else None


def check(directory, tol=0.25, strict_cpu=False, mem_tol=0.25,
          out=sys.stderr):
    rounds = load_rounds(directory)
    if not rounds:
        print(f"trend: no BENCH_r*.json under {directory}", file=out)
        return 1
    errors, warnings = [], []
    # (metric, backend) -> (round_name, value, unit) of last FRESH line
    last_fresh = {}
    # (subject, backend) -> (round_name, value) of the cost-model trends
    last_mem = {}
    last_mfu = {}
    # (subject, backend, field) -> (round_name, value) of the
    # comm-overlap trends (schema v9 fields on attribution/profile
    # metric lines)
    last_overlap = {}
    # (metric, backend) -> (round_name, cold_compile_ms) of the
    # compile-plane trend (schema v10)
    last_compile = {}
    # (metric, backend) -> (round_name, slo_attainment) of the
    # per-tenant attainment trend (schema v11)
    last_attain = {}
    # (metric, backend) -> (round_name, kv_waste_bytes) of the
    # KV-plane trend (schema v12)
    last_waste = {}
    # (metric, backend) -> (round_name, slo_attainment) of the
    # per-class attainment trend (schema v14)
    last_class_attain = {}
    # (entry_point, backend) -> (round_name, replicated_bytes) of the
    # replication-ledger trend (schema v13)
    last_repl = {}
    n_fresh = n_stale = 0

    def track_cost_fields(rname, rec):
        """Peak-memory and MFU trends for one fresh record (bench line
        or ``kind: memory`` dump).  peak_bytes is compiled-plan
        deterministic -> gates on every backend; mfu is timing ->
        follows the CPU-warns policy."""
        subject = _mem_subject(rec)
        if subject is None:
            return
        key = (subject, rec.get("backend"))
        peak = rec.get("peak_bytes")
        if isinstance(peak, (int, float)) and not isinstance(peak, bool) \
                and peak > 0:
            prev = last_mem.get(key)
            if prev is not None:
                pname, pval = prev
                growth = (peak - pval) / pval
                if growth > mem_tol:
                    errors.append(
                        f"{rname}: {subject} "
                        f"[{rec.get('backend') or '?'}] peak memory "
                        f"grew {growth * 100:.0f}% vs {pname} "
                        f"({pval} -> {peak} bytes, mem-tol "
                        f"{mem_tol * 100:.0f}%) — the compiled plan "
                        f"reserves more device memory")
            last_mem[key] = (rname, float(peak))
        mfu = rec.get("mfu")
        if isinstance(mfu, (int, float)) and not isinstance(mfu, bool) \
                and mfu > 0:
            prev = last_mfu.get(key)
            if prev is not None:
                pname, pval = prev
                drop = (pval - mfu) / pval
                if drop > tol:
                    msg = (f"{rname}: {subject} "
                           f"[{rec.get('backend') or '?'}] MFU "
                           f"regressed {drop * 100:.0f}% vs {pname} "
                           f"({pval:.4g} -> {mfu:.4g}, tol "
                           f"{tol * 100:.0f}%)")
                    if is_cpu(rec) and not strict_cpu:
                        warnings.append(msg + " [cpu smoke: warning "
                                        "only]")
                    else:
                        errors.append(msg)
            last_mfu[key] = (rname, float(mfu))

    def track_overlap_fields(rname, rec):
        """Comm-overlap trends for one fresh metric line: the overlap
        fractions (higher is better — the whole point of ROADMAP
        item 2) and the visible-comm time (lower is better).  Both are
        timing-derived, so they follow the accelerator-gates /
        CPU-warns policy like MFU; a zero baseline never trends (no
        overlap yet = nothing to lose)."""
        subject = rec.get("metric")
        if not isinstance(subject, str) or not subject:
            return
        ctx = rec.get("compute_twin_excess_ms")
        if isinstance(ctx, (int, float)) and not isinstance(ctx, bool) \
                and ctx > 0:
            # the attribution flagged its own compute twin as slower
            # than the full step (oversubscribed-host rendezvous
            # staggering): the clamp forces comm_ms=0 /
            # overlap_fraction=1.0 on that record, and seeding the
            # baseline with those perfect-overlap numbers would gate
            # the NEXT healthy round as a phantom regression
            return
        for field, better in (("overlap_fraction", "higher"),
                              ("measured_overlap_fraction", "higher"),
                              ("comm_visible_ms", "lower")):
            val = rec.get(field)
            if (not isinstance(val, (int, float))
                    or isinstance(val, bool) or val < 0):
                continue
            key = (subject, rec.get("backend"), field)
            prev = last_overlap.get(key)
            last_overlap[key] = (rname, float(val))
            if prev is None:
                continue
            pname, pval = prev
            if pval <= 0:
                # a zero baseline means opposite things per direction:
                # a FRACTION at 0 is today's no-overlap world — nothing
                # to lose, never trends.  A lower-is-better TIME at 0
                # is the success state, and comm returning from fully
                # hidden to visibly on the critical path is the WORST
                # regression this column exists for — gate it (0.05 ms
                # absorbs the 4-decimal rounding noise of a true zero).
                if better == "lower" and val > 0.05:
                    msg = (f"{rname}: {subject} "
                           f"[{rec.get('backend') or '?'}] {field} "
                           f"returned from a zero baseline to "
                           f"{val:.4g} vs {pname} — comm is back on "
                           f"the critical path")
                    if is_cpu(rec) and not strict_cpu:
                        warnings.append(msg + " [cpu smoke: warning "
                                        "only]")
                    else:
                        errors.append(msg)
                continue
            if better == "higher":
                change = (pval - val) / pval   # + = less overlap
                verb = "dropped"
            else:
                change = (val - pval) / pval   # + = more visible comm
                verb = "grew"
            if change > tol:
                msg = (f"{rname}: {subject} "
                       f"[{rec.get('backend') or '?'}] {field} {verb} "
                       f"{change * 100:.0f}% vs {pname} "
                       f"({pval:.4g} -> {val:.4g}, tol "
                       f"{tol * 100:.0f}%) — comm is sliding back "
                       f"onto the critical path")
                if is_cpu(rec) and not strict_cpu:
                    warnings.append(msg + " [cpu smoke: warning only]")
                else:
                    errors.append(msg)

    def track_compile_fields(rname, rec):
        """Compile-plane gates for one fresh metric line (schema v10).
        A nonzero steady-state retrace count gates on EVERY backend —
        the ledger counting traces during the timed loop is
        deterministic, so there is no noise excuse; cold_compile_ms
        growth is wall-clock and follows the accelerator-gates /
        CPU-warns policy."""
        subject = rec.get("metric")
        if not isinstance(subject, str) or not subject:
            return
        ssr = rec.get("steady_state_retraces")
        if isinstance(ssr, int) and not isinstance(ssr, bool) and ssr > 0:
            errors.append(
                f"{rname}: {subject} [{rec.get('backend') or '?'}] "
                f"measured {ssr} steady-state retrace(s) — the timed "
                f"loop re-traced a jit entry, so the trended rate "
                f"includes a recompile (the zero-retrace contract "
                f"this line must hold; see /compilez for the culprit "
                f"signature)")
        cc = rec.get("cold_compile_ms")
        if (not isinstance(cc, (int, float)) or isinstance(cc, bool)
                or cc <= 0):
            return
        key = (subject, rec.get("backend"))
        prev = last_compile.get(key)
        last_compile[key] = (rname, float(cc))
        if prev is None:
            return
        pname, pval = prev
        if pval <= 0:
            return
        growth = (cc - pval) / pval
        if growth > tol:
            msg = (f"{rname}: {subject} "
                   f"[{rec.get('backend') or '?'}] cold_compile_ms "
                   f"grew {growth * 100:.0f}% vs {pname} "
                   f"({pval:.4g} -> {cc:.4g} ms, tol "
                   f"{tol * 100:.0f}%) — the compile plane regressed "
                   f"(new shape family, persistent cache stopped "
                   f"hitting, or a slower lowering)")
            if is_cpu(rec) and not strict_cpu:
                warnings.append(msg + " [cpu smoke: warning only]")
            else:
                errors.append(msg)

    def track_tenant_fields(rname, rec):
        """Tenant-plane gates for one fresh metric line (schema v11).
        Per-tenant goodput trends through the ordinary (metric,
        backend) path — the tenant is in the metric name — so this
        adds the two tenant-specific columns: ``slo_attainment``
        (timing-derived, so a drop past ``--tol`` follows the
        accelerator-gates / CPU-warns policy) and the parity check
        (exact token accounting: the two-tenant leg tags every
        request, so a parity off 1.0 is a deterministic split bug —
        gates on every backend, the steady-state-retrace rule)."""
        subject = rec.get("metric")
        if not isinstance(subject, str) or not subject:
            return
        if subject.endswith("_tenant_parity"):
            val = rec.get("value")
            if (isinstance(val, (int, float))
                    and not isinstance(val, bool)
                    and abs(val - 1.0) > 0.01):
                errors.append(
                    f"{rname}: {subject} "
                    f"[{rec.get('backend') or '?'}] tenant parity is "
                    f"{val:.4g}, not 1.0 — the per-tenant split lost "
                    f"or double-counted goodput tokens (every request "
                    f"in the leg is tagged, so the sums must agree "
                    f"exactly)")
            return
        if "tenant" not in rec:
            return
        att = rec.get("slo_attainment")
        if (not isinstance(att, (int, float)) or isinstance(att, bool)
                or not (0.0 <= att <= 1.0)):
            return
        key = (subject, rec.get("backend"))
        prev = last_attain.get(key)
        last_attain[key] = (rname, float(att))
        if prev is None:
            return
        pname, pval = prev
        if pval <= 0:
            return
        drop = (pval - att) / pval
        if drop > tol:
            msg = (f"{rname}: {subject} "
                   f"[{rec.get('backend') or '?'}] slo_attainment "
                   f"dropped {drop * 100:.0f}% vs {pname} "
                   f"({pval:.4g} -> {att:.4g}, tol "
                   f"{tol * 100:.0f}%) — this tenant's deadlines "
                   f"stopped holding")
            if is_cpu(rec) and not strict_cpu:
                warnings.append(msg + " [cpu smoke: warning only]")
            else:
                errors.append(msg)

    def track_qos_fields(rname, rec):
        """QoS-plane gates for one fresh metric line (schema v14).
        Three columns: the preemption-parity check (exact token
        equality of a preempted-then-readmitted request vs an
        undisturbed run — deterministic, gates on every backend, the
        steady-state-retrace rule), the per-class ``slo_attainment``
        trend (timing-derived: accelerator gates, CPU warns, the
        tenant rule), and the aggregate-goodput overhead bound (the
        QoS pass's ``vs_baseline`` vs the untagged pass must stay
        >= 0.95 — timing-derived, same policy)."""
        subject = rec.get("metric")
        if not isinstance(subject, str) or not subject:
            return
        if subject.endswith("_preemption_parity"):
            val = rec.get("value")
            if (isinstance(val, (int, float))
                    and not isinstance(val, bool)
                    and abs(val - 1.0) > 0.01):
                errors.append(
                    f"{rname}: {subject} "
                    f"[{rec.get('backend') or '?'}] preemption parity "
                    f"is {val:.4g}, not 1.0 — a preempted request's "
                    f"replayed tokens diverged from the undisturbed "
                    f"run (eviction perturbed decode state: blocks "
                    f"not recycled cleanly, or the sampling stream "
                    f"is not request-intrinsic); exactness is "
                    f"deterministic, so this gates on every backend")
            return
        if subject.endswith("_qos_aggregate_goodput"):
            vb = rec.get("vs_baseline")
            if (isinstance(vb, (int, float))
                    and not isinstance(vb, bool) and vb < 0.95):
                msg = (f"{rname}: {subject} "
                       f"[{rec.get('backend') or '?'}] QoS aggregate "
                       f"goodput is {vb:.3g}x the untagged baseline "
                       f"(bound 0.95) — the WFQ plane is taxing total "
                       f"throughput beyond its ~5% allowance")
                if is_cpu(rec) and not strict_cpu:
                    warnings.append(msg + " [cpu smoke: warning only]")
                else:
                    errors.append(msg)
            return
        if "qos_class" not in rec:
            return
        att = rec.get("slo_attainment")
        if (not isinstance(att, (int, float)) or isinstance(att, bool)
                or not (0.0 <= att <= 1.0)):
            return
        key = (subject, rec.get("backend"))
        prev = last_class_attain.get(key)
        last_class_attain[key] = (rname, float(att))
        if prev is None:
            return
        pname, pval = prev
        if pval <= 0:
            return
        drop = (pval - att) / pval
        if drop > tol:
            msg = (f"{rname}: {subject} "
                   f"[{rec.get('backend') or '?'}] slo_attainment "
                   f"dropped {drop * 100:.0f}% vs {pname} "
                   f"({pval:.4g} -> {att:.4g}, tol "
                   f"{tol * 100:.0f}%) — this priority class's "
                   f"deadlines stopped holding (did the flood start "
                   f"starving it?)")
            if is_cpu(rec) and not strict_cpu:
                warnings.append(msg + " [cpu smoke: warning only]")
            else:
                errors.append(msg)

    def track_sharding_fields(rname, rec):
        """Replication-ledger gate for one fresh ``kind: sharding``
        record (schema v13).  ``replicated_bytes`` is statically
        derived from the traced jaxpr — deterministic on every
        backend, the peak_bytes rule, not the MFU rule — so growth
        past ``--mem-tol`` gates per (entry_point, backend)
        everywhere; shrinkage is the ZeRO direction and never
        gates."""
        subject = rec.get("entry_point")
        if not isinstance(subject, str) or not subject:
            return
        repl = rec.get("replicated_bytes")
        if (not isinstance(repl, (int, float)) or isinstance(repl, bool)
                or repl < 0):
            return
        key = (subject, rec.get("backend"))
        prev = last_repl.get(key)
        last_repl[key] = (rname, float(repl))
        if prev is None:
            return
        pname, pval = prev
        if pval <= 0:
            # nothing replicated is the fully-sharded success state;
            # duplicate bytes returning from 0 is the regression the
            # ledger exists to catch
            if repl > 0:
                errors.append(
                    f"{rname}: {subject} "
                    f"[{rec.get('backend') or '?'}] replicated_bytes "
                    f"returned from a zero baseline to {repl:,.0f} vs "
                    f"{pname} — something un-sharded (the ledger is "
                    f"static, so this is a real graph change)")
            return
        growth = (repl - pval) / pval
        if growth > mem_tol:
            errors.append(
                f"{rname}: {subject} "
                f"[{rec.get('backend') or '?'}] replicated_bytes grew "
                f"{growth * 100:.0f}% vs {pname} ({pval:,.0f} -> "
                f"{repl:,.0f} bytes, mem-tol {mem_tol * 100:.0f}%) — "
                f"more world bytes are duplicate copies (a ZeRO shard "
                f"re-replicated, or optimizer state stopped "
                f"partitioning); the ledger is deterministic, so this "
                f"gates on every backend")

    def track_kv_fields(rname, rec):
        """KV-plane gates for one fresh metric line (schema v12).
        Two halves: the ``kv_waste_bytes`` trend (lower is better —
        the paged allocator's whole purpose; growth past ``--tol``
        follows the accelerator-gates / CPU-warns policy because the
        sampled waste depends on where in the admit/finish cycle the
        snapshot lands) and the block-pool FIELD contract, which is
        deterministic: a fresh engine_decode line in a round that
        declares schema_version >= 12 without ``admission_mode`` —
        or a paged line without its block fields — gates on every
        backend (archived rounds declaring an older version are
        exempt; they were valid when written)."""
        subject = rec.get("metric")
        if not isinstance(subject, str) or not subject:
            return
        sv = rec.get("schema_version")
        declared_v12 = isinstance(sv, int) and not isinstance(sv, bool) \
            and sv >= 12
        if declared_v12 and "engine_decode" in subject:
            mode = rec.get("admission_mode")
            if mode is None:
                errors.append(
                    f"{rname}: {subject} "
                    f"[{rec.get('backend') or '?'}] declares schema "
                    f"v{sv} but carries no admission_mode — every "
                    f"fresh v12 engine line must say which allocator "
                    f"(fixed_slot | paged) produced it")
            elif mode == "paged":
                missing = [f for f in ("block_size", "blocks_total",
                                       "blocks_free")
                           if not isinstance(rec.get(f), int)
                           or isinstance(rec.get(f), bool)]
                if missing:
                    errors.append(
                        f"{rname}: {subject} "
                        f"[{rec.get('backend') or '?'}] is a paged "
                        f"engine line missing {missing} — v12 paged "
                        f"lines must expose the block pool")
        waste = rec.get("kv_waste_bytes")
        if (not isinstance(waste, (int, float))
                or isinstance(waste, bool) or waste < 0):
            return
        key = (subject, rec.get("backend"))
        prev = last_waste.get(key)
        last_waste[key] = (rname, float(waste))
        if prev is None:
            return
        pname, pval = prev
        if pval <= 0:
            # zero waste is the success state (a well-sized block
            # pool); waste returning from 0 to measurably nonzero is
            # the regression this column exists to catch
            if waste > 0:
                msg = (f"{rname}: {subject} "
                       f"[{rec.get('backend') or '?'}] kv_waste_bytes "
                       f"returned from a zero baseline to "
                       f"{waste:.4g} vs {pname} — the KV pool is "
                       f"fragmenting again (block_size too large, or "
                       f"blocks leaking)")
                if is_cpu(rec) and not strict_cpu:
                    warnings.append(msg + " [cpu smoke: warning only]")
                else:
                    errors.append(msg)
            return
        growth = (waste - pval) / pval
        if growth > tol:
            msg = (f"{rname}: {subject} "
                   f"[{rec.get('backend') or '?'}] kv_waste_bytes "
                   f"grew {growth * 100:.0f}% vs {pname} "
                   f"({pval:.4g} -> {waste:.4g} bytes, tol "
                   f"{tol * 100:.0f}%) — KV fragmentation is trending "
                   f"the wrong way (block_size too large, or blocks "
                   f"leaking)")
            if is_cpu(rec) and not strict_cpu:
                warnings.append(msg + " [cpu smoke: warning only]")
            else:
                errors.append(msg)

    for rname, recs in rounds:
        for rec in recs:
            # ``kind: memory`` records are not throughput measurements
            # but carry the peak-bytes trend; stale replays stay out
            if isinstance(rec, dict) and rec.get("kind") == "memory":
                if is_stale(rec):
                    n_stale += 1
                elif "error" not in rec:
                    track_cost_fields(rname, rec)
                continue
            # ``kind: sharding`` records carry the replication-ledger
            # trend (schema v13); stale replays stay out as ever
            if isinstance(rec, dict) and rec.get("kind") == "sharding":
                if is_stale(rec):
                    n_stale += 1
                elif "error" not in rec:
                    track_sharding_fields(rname, rec)
                continue
            # ``kind: numerics`` records (gradient-health dumps from
            # bench --numerics) describe one run's numerics, not a
            # cross-round trend; stale replays partition out as ever.
            # ``kind: run`` records (supervisor verdicts from bench
            # --run, schema v5) likewise describe one run — its
            # anomaly counts are that run's story, not a regression
            # against an earlier round's run.  ``kind: recovery``
            # records (controller snapshots from bench --chaos,
            # schema v6) are the same shape of story: the METRIC
            # lines next to them (chaos_mttr*, chaos_spike*) carry
            # the cross-round trend.  ``kind: profile`` records
            # (device-timeline attributions from bench --profile /
            # /profilez, schema v8) likewise describe one capture —
            # the profile_* metric lines next to them trend.
            # ``kind: fleet`` snapshots (and their v11 per-tenant
            # blocks) describe one run's traffic mix — the tenant
            # metric lines next to them trend.
            if isinstance(rec, dict) and rec.get("kind") in ("numerics",
                                                             "run",
                                                             "recovery",
                                                             "profile",
                                                             "fleet"):
                if is_stale(rec):
                    n_stale += 1
                continue
            if not is_measurement(rec):
                continue
            if is_stale(rec):
                n_stale += 1
                continue              # never enters the trend
            n_fresh += 1
            track_cost_fields(rname, rec)
            track_overlap_fields(rname, rec)
            track_compile_fields(rname, rec)
            track_tenant_fields(rname, rec)
            track_kv_fields(rname, rec)
            track_qos_fields(rname, rec)
            key = (rec["metric"], rec.get("backend"))
            prev = last_fresh.get(key)
            if prev is not None:
                pname, pval, _ = prev
                val = float(rec["value"])
                if pval > 0 and val > 0:
                    # relative-to-previous in BOTH directions, so the
                    # printed percent is the actual worsening and the
                    # effective tolerance doesn't depend on whether
                    # the metric is a time or a rate
                    if direction(rec) == "lower":
                        change = (val - pval) / pval  # + = slower = worse
                    else:
                        change = (pval - val) / pval  # + = less = worse
                    if change > tol:
                        msg = (f"{rname}: {rec['metric']} "
                               f"[{rec.get('backend') or '?'}] "
                               f"regressed {change * 100:.0f}% vs "
                               f"{pname} ({pval} -> {val} "
                               f"{rec.get('unit')}, tol "
                               f"{tol * 100:.0f}%)")
                        if is_cpu(rec) and not strict_cpu:
                            warnings.append(msg + " [cpu smoke: "
                                            "warning only]")
                        else:
                            errors.append(msg)
            last_fresh[key] = (rname, float(rec["value"]),
                               rec.get("unit"))
    for w in warnings:
        print(f"trend WARNING: {w}", file=out)
    for e in errors:
        print(f"trend ERROR: {e}", file=out)
    print(f"trend: {len(rounds)} rounds, {n_fresh} fresh measurements "
          f"counted, {n_stale} stale replays partitioned out, "
          f"{len(warnings)} warnings, {len(errors)} errors", file=out)
    return 1 if errors else 0


def main(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--dir", default=_ROOT,
                    help="directory holding BENCH_r*.json "
                         "(default: repo root)")
    ap.add_argument("--tol", type=float, default=0.25,
                    help="fresh-vs-fresh regression tolerance "
                         "(fraction, default 0.25)")
    ap.add_argument("--strict-cpu", action="store_true",
                    help="gate CPU-smoke regressions too (default: "
                         "warn only — the shared CPU host is noisy)")
    ap.add_argument("--mem-tol", type=float, default=0.25,
                    help="peak-memory growth tolerance (fraction, "
                         "default 0.25; gates on every backend — the "
                         "compiled plan is deterministic)")
    args = ap.parse_args(argv[1:])
    if args.tol < 0:
        ap.error(f"--tol must be >= 0, got {args.tol}")
    if args.mem_tol < 0:
        ap.error(f"--mem-tol must be >= 0, got {args.mem_tol}")
    return check(args.dir, tol=args.tol, strict_cpu=args.strict_cpu,
                 mem_tol=args.mem_tol)


if __name__ == "__main__":
    sys.exit(main(sys.argv))
