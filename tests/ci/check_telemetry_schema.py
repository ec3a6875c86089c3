#!/usr/bin/env python
"""CI gate: validate a JSONL telemetry stream against the record schemas.

Every line must be a JSON object carrying ``schema_version``, the
capture host, a boolean ``stale`` field and a ``kind`` that
``apex_tpu/observability/exporters.py::validate_telemetry_record``
knows: graph-lint findings and their summary (``python -m
apex_tpu.analysis``, ``tests/ci/graph_lint.py``), cost-model dumps
(``kind: memory``, ``--memory``), replication ledgers (``kind:
sharding``, ``--sharding``), and what a program writes from
``Fleet.record()``, ``SpanRecorder.trace_record``,
``NumericsMonitor.to_record``, ``RunSupervisor.record`` and
``RecoveryLog.record``.  A record that declares another
``schema_version`` than the current one is refused.  The kinds may
interleave in one stream; a line without a known ``kind`` is an error.
Usage:

    python -m apex_tpu.analysis | python tests/ci/check_telemetry_schema.py
    python -m apex_tpu.analysis --sharding \
        | python tests/ci/check_telemetry_schema.py
    python tests/ci/graph_lint.py | python tests/ci/check_telemetry_schema.py
    python tests/ci/check_telemetry_schema.py records.jsonl

Exit status 0 = every record valid; 1 = any schema violation (each is
printed).  Stderr chatter must not be piped in: the producers keep
stdout pure JSONL.
"""

import importlib.util
import os
import sys

_ROOT = os.path.abspath(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), os.pardir, os.pardir))


def _load_exporters():
    """Load observability.exporters WITHOUT importing the apex_tpu
    package: the validator is pure stdlib, and a schema gate that pulls
    in jax + the full model zoo would cost ~15s per CI invocation for
    nothing."""
    pkg_dir = os.path.join(_ROOT, "apex_tpu", "observability")
    spec = importlib.util.spec_from_file_location(
        "_obs_schema", os.path.join(pkg_dir, "__init__.py"),
        submodule_search_locations=[pkg_dir])
    pkg = importlib.util.module_from_spec(spec)
    sys.modules["_obs_schema"] = pkg
    for sub in ("metrics", "exporters"):
        sspec = importlib.util.spec_from_file_location(
            f"_obs_schema.{sub}", os.path.join(pkg_dir, sub + ".py"))
        mod = importlib.util.module_from_spec(sspec)
        sys.modules[f"_obs_schema.{sub}"] = mod
        sspec.loader.exec_module(mod)
    return sys.modules["_obs_schema.exporters"]


def main(argv):
    validate_telemetry_jsonl = _load_exporters().validate_telemetry_jsonl
    if len(argv) > 1:
        with open(argv[1]) as f:
            lines = f.readlines()
    else:
        lines = sys.stdin.readlines()
    errs = validate_telemetry_jsonl(lines)
    for e in errs:
        print(f"check_telemetry_schema: {e}", file=sys.stderr)
    if errs:
        return 1
    n = sum(1 for ln in lines if ln.strip())
    print(f"check_telemetry_schema: {n} records OK")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
