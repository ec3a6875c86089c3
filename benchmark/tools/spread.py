#!/usr/bin/env python3
"""Medians and quartile spreads of the result lines that tools/runset.sh
gathered, one file per set: what PERF.md's table of bounds quotes.

    python benchmark/tools/spread.py chiprun_out/c1_A.jsonl chiprun_out/c1_B.jsonl
"""

import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from lib import stats


def main():
    for path in sys.argv[1:]:
        rows = [json.loads(l) for l in open(path) if l.startswith('{"correct"')]
        print(path, "runs", len(rows), "correct", [r["correct"] for r in rows],
              "attempted", [r["attempted"] for r in rows], "failed", sum(r["failed"] for r in rows))
        for name in rows[0]["metrics"]:
            values = [r["metrics"][name]["value"] for r in rows]
            kept = values[1:] if name == "setup_s" else values     # a set's first run may compile
            spread = stats.iqr_spread(kept) if len(kept) >= 2 else None
            print("  ", name, "median", statistics.median(kept), "spread", spread, "values", values)


if __name__ == "__main__":
    main()
