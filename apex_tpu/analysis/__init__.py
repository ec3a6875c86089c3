"""apex_tpu.analysis — static analysis over jaxprs and lowered
StableHLO that pins every hot-path invariant.

Apex's value is that mixed precision and data parallelism stay correct
*by construction* — a single silently-upcast conv or a botched buffer
donation erases the win the framework exists to deliver.  This package
enforces those invariants mechanically:

- a **rule engine** (:mod:`.core`, :mod:`.rules`): host-transfer,
  donation (incl. the per-slot length-vector blocklist and
  double-donation of shared buffers), amp dtype policy, channels-last
  layout, and collective accounting;
- an **entry-point registry** (:mod:`.entry_points`) tracing the real
  graphs the examples and the serving engines execute;
- machine-readable findings exported as schema-versioned JSONL through
  ``observability.exporters`` — shared by the tests
  (tests/test_step_graph_audit.py), the CI gate
  (tests/ci/graph_lint.py) and the CLI::

      python -m apex_tpu.analysis            # lint every entry point
      python -m apex_tpu.analysis --list     # what would run
      python -m apex_tpu.analysis --tags serving --rules donation

See docs/analysis.md for the rule catalogue and how to add a rule.
"""

from .core import (Finding, Rule, RULES, register_rule, get_rule,
                   analyze, analyze_entry_point, findings_to_records,
                   run_lint, ERROR, WARNING)
from .graphs import (HOST_TRANSFER_PRIMS, COLLECTIVE_PRIMS, Graph,
                     walk_jaxpr, prim_eqns, host_transfer_eqns,
                     conv_eqns, large_dot_eqns, transpose_eqns,
                     collective_eqns, eqn_payload_bytes, lowered_text,
                     aliased_output_count, donated_arg_names,
                     duplicate_donated_leaves)
from .entry_points import (EntryPoint, ENTRY_POINTS,
                           register_entry_point, get, select,
                           entry_point_memory_record)
from .sharding import (Partition, ArgSharding, CollectiveSite,
                       ShardMapAnalysis, RESHARD_PRIMS, shard_map_eqns,
                       analyze_shard_map, analyze_sharding,
                       check_shard_map_specs, divergent_output_claims,
                       entry_point_sharding_record)
from .pallas_lint import (KernelSite, capture_kernel_sites, check_site,
                          collect_kernel_sites, lint_pallas_kernels)
from . import rules  # noqa: F401  (registers the core rule set)
from . import core
from . import graphs
from . import entry_points
from . import sharding
from . import pallas_lint

__all__ = [
    "Finding", "Rule", "RULES", "register_rule", "get_rule",
    "analyze", "analyze_entry_point", "findings_to_records",
    "run_lint", "ERROR", "WARNING",
    "HOST_TRANSFER_PRIMS", "COLLECTIVE_PRIMS", "Graph",
    "walk_jaxpr", "prim_eqns", "host_transfer_eqns", "conv_eqns",
    "large_dot_eqns", "transpose_eqns", "collective_eqns",
    "eqn_payload_bytes", "lowered_text", "aliased_output_count",
    "donated_arg_names", "duplicate_donated_leaves",
    "EntryPoint", "ENTRY_POINTS", "register_entry_point", "get",
    "select", "rules", "core", "graphs", "entry_points",
    "Partition", "ArgSharding", "CollectiveSite", "ShardMapAnalysis",
    "RESHARD_PRIMS", "shard_map_eqns", "analyze_shard_map",
    "analyze_sharding", "check_shard_map_specs",
    "divergent_output_claims", "entry_point_sharding_record",
    "sharding",
    "KernelSite", "capture_kernel_sites", "check_site",
    "collect_kernel_sites", "lint_pallas_kernels", "pallas_lint",
]
