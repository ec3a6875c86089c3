#!/usr/bin/env python
"""CI gate: run the serving + fleet suites TWICE against ONE
persistent compile-cache dir — and MEASURE that run 2 reloaded.

Why twice: the PR 2 donation gotcha.  On XLA:CPU (seen under jax
0.4.37; not re-derived since), donating the wrong argnum class (the per-slot length vectors,
``serving.DONATION_BLOCKLIST``) produces executables that work when
freshly compiled but decode garbage when RELOADED from the persistent
compilation cache — so a single green run proves nothing about the
next warm one.  Run 1 populates a dedicated cache dir; run 2 executes
the very same jitted mutators from AOT-reloaded executables.  Both
must pass.  The static donation rule (apex_tpu/analysis) pins the
blocklist structurally; this gate pins the runtime behavior.

The compilation ledger turns "both runs green" from an
absence-of-garbage check into a POSITIVE measurement: each run dumps
its ledger at session end (conftest's
``APEX_TPU_COMPILATION_LEDGER_DUMP`` hook), and this gate asserts run
2's serving entries (``engine.*`` / ``seq2seq.*``) compiled with
**zero persistent-cache misses and at least one hit** — i.e. the warm
run really executed AOT-reloaded executables rather than silently
recompiling everything fresh (which would also "pass" while proving
nothing about the reload path).  ``APEX_TPU_COMPILE_CACHE_MIN_S=0``
makes every compile cacheable so sub-threshold toy compiles cannot
spoil the measurement.  When NEITHER run saw a single cache event
(jax.monitoring's cache events unavailable on the backend/version —
the condition the pytest suite skips on), the measurement is reported
as unavailable and only the behavioral both-runs-green gate applies.

Usage:

    python tests/ci/double_run.py             # fixed dir, emptied first
    python tests/ci/double_run.py /some/dir   # persistent across CI runs

The default directory is ``<checkout>/.jax_compile_cache/double_run`` —
fixed, because the children find the cache through the standard
``JAX_COMPILATION_CACHE_DIR`` and a moving path never hits — and it is
emptied before run 1 so that run really is cold.

Extra pytest args go after ``--``:

    python tests/ci/double_run.py -- -x -q

Exit status 0 = both runs green AND run 2 ledger-measured cache-HIT;
nonzero otherwise.
"""

import json
import os
import shutil
import subprocess
import sys

_ROOT = os.path.abspath(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), os.pardir, os.pardir))

# the suites exercising every donated cache mutator: the engines
# directly, and the fleet driving many engine instances (each with its
# own jit closures -> its own cache entries)
SUITES = ["tests/test_serving.py", "tests/test_fleet.py"]

# ledger entries owned by the serving engines (the donated mutators
# this gate exists for) — fleet helpers and model-level jits
# outside the engines are not part of the reload contract
SERVING_ENTRY_PREFIXES = ("engine.", "seq2seq.")

# the paged engine's jitted executables get their own named assertion:
# they are the newest donated mutators (block-pool KV, schema v12) and
# the exact class the PR 2 reload regression bites — run 2 must reload
# them from the persistent cache, not merely "some serving entry"
PAGED_ENTRIES = ("engine._paged_step_k", "engine._paged_admit")


def _serving_cache_counts(dump_path):
    """(hits, misses, uncached, entries, per_entry) summed over the
    serving entries of one run's ledger dump; None when the dump is
    missing or unreadable (reported by the caller)."""
    try:
        with open(dump_path) as f:
            snap = json.load(f)
    except (OSError, ValueError) as e:
        print(f"double_run: cannot read ledger dump {dump_path}: {e}",
              file=sys.stderr)
        return None
    hits = misses = uncached = 0
    names = []
    per = {}
    for name, st in snap.get("entries", {}).items():
        if not name.startswith(SERVING_ENTRY_PREFIXES):
            continue
        cache = st.get("cache", {})
        h = int(cache.get("hit", 0))
        m = int(cache.get("miss", 0))
        u = int(cache.get("uncached", 0))
        hits += h
        misses += m
        uncached += u
        names.append(name)
        per[name] = (h, m, u)
    return hits, misses, uncached, sorted(names), per


def check_cache_hits(run1_dump, run2_dump):
    """The positive reload measurement: run 2's serving compiles must
    be persistent-cache HITS — zero misses, at least one hit.  Returns
    a list of problems (empty = measured clean)."""
    errs = []
    c1 = _serving_cache_counts(run1_dump)
    c2 = _serving_cache_counts(run2_dump)
    if c1 is None or c2 is None:
        return ["ledger dump missing — conftest's "
                "APEX_TPU_COMPILATION_LEDGER_DUMP hook did not fire"]
    h1, m1, u1, names1, _ = c1
    h2, m2, u2, names2, per2 = c2
    if not names2:
        return ["run 2 ledger recorded no serving entries — the "
                "engines' jits are no longer instrumented?"]
    if h1 == m1 == 0 and h2 == m2 == 0 and (u1 or u2):
        # NEITHER run saw a single cache event: jax.monitoring's
        # /jax/compilation_cache/* events are not firing on this
        # backend/version (the same condition the pytest suite
        # skips on).  That is "measurement unavailable", not "cache
        # missed" — both runs still passed, which is the original
        # absence-of-garbage gate; warn instead of going
        # permanently red on an environment drift.
        print("double_run: WARNING — no persistent-cache "
              "attribution in either run (jax.monitoring cache "
              "events unavailable?); the run-2 cache-HIT "
              "measurement was skipped, the behavioral double-run "
              "gate still passed", file=sys.stderr)
        return []
    if u2:
        errs.append(f"run 2 had {u2} serving compile(s) with no "
                    f"cache attribution — is the persistent cache "
                    f"disabled? (run 1: hits={h1} misses={m1} "
                    f"uncached={u1})")
    if m2 > 0:
        errs.append(f"run 2 had {m2} serving cache MISS(es) — the "
                    f"warm run recompiled instead of reloading "
                    f"(entries: {names2}); the AOT-reload gate "
                    f"measured nothing for those executables")
    if m2 == 0 and h2 == 0:
        errs.append("run 2 recorded serving compiles but zero cache "
                    "hits and zero misses — attribution is broken")
    # the paged executables by name: each must be present in run 2 and
    # reload as pure hits (>=1 hit, 0 misses) — the aggregate check
    # above could be satisfied by the fixed-slot engine alone
    for pname in PAGED_ENTRIES:
        if pname not in per2:
            errs.append(f"run 2 ledger has no entry for {pname} — "
                        f"the paged engine's jit is no longer "
                        f"instrumented or the suites stopped "
                        f"exercising it")
            continue
        ph, pm, pu = per2[pname]
        if pm > 0 or (ph == 0 and pu > 0):
            errs.append(f"run 2: {pname} compiled with hits={ph} "
                        f"misses={pm} uncached={pu} — the paged "
                        f"executable did not reload from the "
                        f"persistent cache")
    if not errs:
        print(f"double_run: run 2 serving suite ledger-measured "
              f"cache-HIT ({h2} hits, 0 misses over "
              f"{len(names2)} entries; run 1 populated with "
              f"{m1} misses)")
    return errs


def main(argv):
    args = argv[1:]
    extra = []
    if "--" in args:
        split = args.index("--")
        args, extra = args[:split], args[split + 1:]
    if args:
        cache_dir = os.path.abspath(args[0])
    else:
        cache_dir = os.path.join(_ROOT, ".jax_compile_cache",
                                 "double_run")
        shutil.rmtree(cache_dir, ignore_errors=True)
    os.makedirs(cache_dir, exist_ok=True)

    env = dict(os.environ)
    env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    env.pop("APEX_TPU_NO_COMPILE_CACHE", None)
    # every compile cacheable: the run-2 HIT assertion must not be
    # spoiled by toy compiles under the default 0.5s write threshold
    env["APEX_TPU_COMPILE_CACHE_MIN_S"] = "0"
    dumps = {run: os.path.join(cache_dir, f"ledger_run{run}.json")
             for run in (1, 2)}

    status = 0
    for run in (1, 2):
        label = ("cold (populates the cache)" if run == 1
                 else "warm (AOT-reloaded executables)")
        print(f"double_run: run {run}/2 — {label}; cache dir "
              f"{cache_dir}", flush=True)
        env["APEX_TPU_COMPILATION_LEDGER_DUMP"] = dumps[run]
        proc = subprocess.run(
            [sys.executable, "-m", "pytest", *SUITES, "-q",
             *(extra or ["-x"])],
            cwd=_ROOT, env=env)
        if proc.returncode != 0:
            print(f"double_run: run {run}/2 FAILED "
                  f"(exit {proc.returncode})"
                  + ("" if run == 1 else
                     " — executables reloaded from the persistent "
                     "compile cache misbehaved; suspect a donation "
                     "change (see serving.DONATION_BLOCKLIST)"),
                  file=sys.stderr)
            status = proc.returncode
            break
    else:
        errs = check_cache_hits(dumps[1], dumps[2])
        for e in errs:
            print(f"double_run: {e}", file=sys.stderr)
        if errs:
            status = 1
        else:
            print("double_run: both runs green — donated "
                  "executables survive the AOT cache round trip, "
                  "and run 2 measurably RELOADED them")
    return status


if __name__ == "__main__":
    sys.exit(main(sys.argv))
