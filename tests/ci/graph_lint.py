#!/usr/bin/env python
"""CI gate: run the static graph analyzer over every registered hot
entry point and fail on ANY finding.

This is the mechanical enforcement of the invariants the repo has paid
to learn: no host syncs in jitted hot graphs, every donated KV buffer
actually aliased (and the per-slot length vectors NEVER donated — the
PR 2 compile-cache corruption), conv/matmul operand dtypes matching
the O-level policy, transpose-free channels-last steps, and the exact
collective pattern DDP/TP assume — plus, since the sharding plane
landed, shard_map specs consistent with their mesh (and every
replicated-out-spec divergence declared) and every placement-changing
collective explained by the comm plan or a declared budget
(resharding census).  New rules registered in apex_tpu.analysis.rules
are picked up here automatically: this gate runs the full RULES
registry via the module CLI.  Usage:

    python tests/ci/graph_lint.py                      # full registry
    python tests/ci/graph_lint.py --tags serving       # subset
    python tests/ci/graph_lint.py --entry paged        # substring
    python tests/ci/graph_lint.py | \\
        python tests/ci/check_telemetry_schema.py       # schema-check it

Stdout is pure schema-versioned JSONL (findings + a summary record);
progress goes to stderr.  Exit 0 = clean, 1 = any finding.  Unlike the
module CLI (``python -m apex_tpu.analysis``), warnings also fail here:
CI has no one to read them.
"""

import json
import os
import sys

_ROOT = os.path.abspath(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), os.pardir, os.pardir))


def main(argv):
    sys.path.insert(0, _ROOT)
    import io
    from apex_tpu.analysis.__main__ import main as lint_main

    args = argv[1:]
    buf = io.StringIO()
    real = sys.stdout
    sys.stdout = buf
    try:
        rc = lint_main(args)
    finally:
        sys.stdout = real
    out = buf.getvalue()
    sys.stdout.write(out)
    sys.stdout.flush()

    # promote warnings to failures by reading the run's own
    # graph_lint_summary record — from the --out file when the stream
    # was redirected there (--out appends, so scan from the end);
    # argparse accepts both "--out PATH" and "--out=PATH"
    out_path = None
    for i, a in enumerate(args):
        if a == "--out" and i + 1 < len(args):
            out_path = args[i + 1]
        elif a.startswith("--out="):
            out_path = a.split("=", 1)[1]
    lines = out.splitlines()
    if out_path:
        with open(out_path) as f:
            lines = f.read().splitlines()
    n_warn = 0
    for ln in reversed(lines):
        if ln.strip():
            rec = json.loads(ln)
            if rec.get("kind") == "graph_lint_summary":
                n_warn = rec.get("warnings", 0)
                break
    if rc == 0 and n_warn:
        print(f"graph_lint: {n_warn} warning(s) — CI treats warnings "
              f"as failures", file=sys.stderr)
        return 1
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv))
