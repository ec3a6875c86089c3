"""CLI: ``python -m apex_tpu.analysis`` — lint the hot graphs.

Stdout is pure schema-versioned JSONL: one
``graph_lint`` record per finding plus one ``graph_lint_summary``
record, all enriched by ``observability.exporters.JsonlExporter`` and
validated by ``tests/ci/check_telemetry_schema.py``.  Human-readable
progress goes to stderr.  Exit status: 0 = clean, 1 = any
error-severity finding (the CI gate tests/ci/graph_lint.py relies on
this), 2 = bad usage.
"""

from __future__ import annotations

import argparse
import os
import sys
import time
from typing import List


def _force_virtual_mesh():
    """Mirror tests/conftest.py: the DDP/TP entry points trace an
    8-device mesh, so force the virtual CPU mesh before the first
    backend initialization.  Jaxpr properties are backend-independent
    — the CPU trace pins what the TPU executable will see.  Set
    APEX_TPU_ANALYSIS_BACKEND=native to lint on the ambient backend
    instead."""
    if os.environ.get("APEX_TPU_ANALYSIS_BACKEND") == "native":
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    # jax is already imported (we live inside the package), so flip the
    # platform via jax.config — effective as long as no backend has
    # been initialized yet (tests/conftest.py's strategy)
    import jax
    jax.config.update("jax_platforms", "cpu")


def main(argv: List[str] = None) -> int:
    _force_virtual_mesh()
    p = argparse.ArgumentParser(
        prog="python -m apex_tpu.analysis",
        description="Static graph lint over the hot entry points.")
    p.add_argument("--entry-points", default=None,
                   help="comma-separated entry-point names "
                        "(default: all registered)")
    p.add_argument("--tags", default=None,
                   help="comma-separated tags to select entry points "
                        "(e.g. training,serving)")
    p.add_argument("--rules", default=None,
                   help="comma-separated rule names (default: all)")
    p.add_argument("--entry", default=None, metavar="SUBSTR",
                   help="substring filter on entry-point names — the "
                        "rule-author iteration loop (the warm full "
                        "registry is ~16-21s; --entry=paged runs just "
                        "the paged engine EPs).  Composes with "
                        "--entry-points/--tags and filters --list")
    p.add_argument("--rule", default=None, metavar="SUBSTR",
                   help="substring filter on rule names (e.g. "
                        "--rule=shard matches sharding + "
                        "resharding-census).  Composes with --rules "
                        "and filters --list")
    p.add_argument("--list", action="store_true",
                   help="list entry points and rules, run nothing")
    p.add_argument("--memory", action="store_true",
                   help="emit one `kind: memory` record per entry "
                        "point (analytic FLOPs/bytes + the compiled "
                        "memory plan) instead of linting.  Compiles "
                        "each selected entry point — combine with "
                        "--entry-points/--tags to bound the cost")
    p.add_argument("--sharding", action="store_true",
                   help="emit one `kind: sharding` record (the "
                        "replication ledger: per-dtype replicated "
                        "bytes, top replicated arrays, resharding "
                        "census) per entry point instead of linting. "
                        "Entry points that trace no shard_map "
                        "(serving engines) are skipped")
    p.add_argument("--out", default=None,
                   help="append JSONL findings to this path instead of "
                        "stdout")
    args = p.parse_args(argv)

    from . import ENTRY_POINTS, RULES, get_rule, run_lint, select

    def _ep_match(name):
        return args.entry is None or args.entry in name

    def _rule_match(name):
        return args.rule is None or args.rule in name

    from ..observability.exporters import JsonlExporter

    if args.list:
        for ep in ENTRY_POINTS.values():
            if _ep_match(ep.name):
                print(f"{ep.name:32s} [{', '.join(sorted(ep.tags))}] "
                      f"{ep.description}")
        print("rules: " + ", ".join(
            r for r in sorted(RULES) if _rule_match(r)))
        return 0

    try:
        eps = select(
            names=args.entry_points.split(",")
            if args.entry_points else None,
            tags=args.tags.split(",") if args.tags else None)
        rules = ([get_rule(r) for r in args.rules.split(",")]
                 if args.rules else None)
    except KeyError as e:
        print(f"graph lint: {e.args[0]}", file=sys.stderr)
        return 2
    eps = [ep for ep in eps if _ep_match(ep.name)]
    if args.rule is not None:
        rules = [r for r in (rules if rules is not None
                             else RULES.values())
                 if _rule_match(r.name)]
        if not rules:
            print(f"no rules match --rule={args.rule}", file=sys.stderr)
            return 2
    if not eps:
        print("no entry points selected", file=sys.stderr)
        return 2

    def progress(ep, findings, dt):
        for f in findings:
            print(f"  {f}", file=sys.stderr)
        print(f"{ep.name:32s} {len(findings)} finding(s) [{dt:.1f}s]",
              file=sys.stderr)

    exp = JsonlExporter(path=args.out) if args.out \
        else JsonlExporter(stream=sys.stdout)

    if args.memory:
        # per-entry-point memory/FLOP dump: the analytic cost model
        # (free: reuses the cached trace) plus the compiled memory
        # plan (pays one compile per entry point, cached per process).
        # Same stdout contract as lint: pure schema-valid JSONL,
        # check_telemetry_schema.py validates the stream.
        from .entry_points import entry_point_memory_record
        failed = 0
        with exp:
            for ep in eps:
                t0 = time.perf_counter()
                try:
                    rec = entry_point_memory_record(ep)
                except RuntimeError as e:
                    # only the bare-RuntimeError device-count gate is a
                    # skip; jaxlib's XlaRuntimeError SUBCLASSES
                    # RuntimeError, and a real compile failure must
                    # fail the gate, not read as "skipped"
                    if type(e) is not RuntimeError:
                        failed += 1
                        print(f"{ep.name:32s} FAILED: {e}",
                              file=sys.stderr)
                        continue
                    print(f"{ep.name:32s} skipped: {e}",
                          file=sys.stderr)
                    continue
                except Exception as e:
                    failed += 1
                    print(f"{ep.name:32s} FAILED: {e}", file=sys.stderr)
                    continue
                exp.emit(rec)
                print(f"{ep.name:32s} flops={rec['flops']:.4g} "
                      f"peak_bytes={rec['peak_bytes']:,} "
                      f"[{time.perf_counter() - t0:.1f}s]",
                      file=sys.stderr)
        return 1 if failed else 0

    if args.sharding:
        # per-entry-point replication ledger: statically derived from
        # the traced jaxpr (free: reuses the cached trace, never
        # compiles).  Same stdout contract as lint: pure schema-valid
        # JSONL.  Two skip classes ride the bare-RuntimeError gate:
        # the device-count gate (hierarchical EPs on a 1-device host)
        # and "traces no shard_map" (serving engines) — jaxlib's
        # XlaRuntimeError SUBCLASSES RuntimeError, so a real trace
        # failure still fails the run.
        from .sharding import entry_point_sharding_record
        failed = 0
        with exp:
            for ep in eps:
                t0 = time.perf_counter()
                try:
                    rec = entry_point_sharding_record(ep)
                except RuntimeError as e:
                    if type(e) is not RuntimeError:
                        failed += 1
                        print(f"{ep.name:32s} FAILED: {e}",
                              file=sys.stderr)
                        continue
                    print(f"{ep.name:32s} skipped: {e}",
                          file=sys.stderr)
                    continue
                except Exception as e:
                    failed += 1
                    print(f"{ep.name:32s} FAILED: {e}", file=sys.stderr)
                    continue
                exp.emit(rec)
                print(f"{ep.name:32s} "
                      f"replicated={rec['replicated_bytes']:,} "
                      f"({rec['replicated_fraction']:.1%} of world "
                      f"bytes) [{time.perf_counter() - t0:.1f}s]",
                      file=sys.stderr)
        return 1 if failed else 0
    t0 = time.perf_counter()
    with exp:
        summary = run_lint(entry_points=eps, rules=rules,
                           emit=exp.emit, progress=progress)
    print(f"graph lint: {summary['entry_points']} entry point(s), "
          f"{summary['errors']} error(s), {summary['warnings']} "
          f"warning(s) in {time.perf_counter() - t0:.1f}s",
          file=sys.stderr)
    return 1 if summary["errors"] else 0


if __name__ == "__main__":
    sys.exit(main())
