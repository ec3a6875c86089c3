"""bench.py's importable pieces: the --comm record contract and the ZeRO
legs' device-count gate."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import bench


def test_comm_bench_record_schema():
    """The --comm microbench record contract: a record carrying
    ``comm_topology`` must state the per-level wire bytes, compression
    flag and level widths, and fresh ``grad_allreduce_*`` metrics must
    carry the topology fields at all (tests/ci/check_bench_schema.py
    rides the same validator)."""
    from apex_tpu.observability import exporters
    good = exporters.JsonlExporter.enrich({
        "metric": "grad_allreduce_hier_step_time", "value": 31.0,
        "unit": "ms", "vs_baseline": None, "backend": "cpu", "ndev": 8,
        "arch": "cpu", "comm_topology": "hierarchical",
        "compress": False, "ici_size": 4, "dcn_size": 2,
        "wire_bytes": 6_000_000, "ici_wire_bytes": 5_000_000,
        "dcn_wire_bytes": 1_000_000})
    assert exporters.validate_bench_record(good) == []
    # a grad_allreduce line with no topology fields is invalid fresh...
    bare = {k: v for k, v in good.items()
            if k not in ("comm_topology", "compress", "ici_size",
                         "dcn_size", "wire_bytes", "ici_wire_bytes",
                         "dcn_wire_bytes")}
    assert any("comm_topology" in e
               for e in exporters.validate_bench_record(bare))
    # ...but a record marked stale (pre-topology) is exempt
    assert exporters.validate_bench_record(dict(bare, stale=True)) == []
    # bad values flag field-by-field
    assert any("comm_topology" in e for e in
               exporters.validate_bench_record(
                   dict(good, comm_topology="diagonal")))
    assert any("dcn_wire_bytes" in e for e in
               exporters.validate_bench_record(
                   dict(good, dcn_wire_bytes=-1)))
    assert any("compress" in e for e in
               exporters.validate_bench_record(
                   dict(good, compress="yes")))
    assert any("ici_size" in e for e in
               exporters.validate_bench_record(dict(good, ici_size=0)))


def test_zero_leg_device_gate_is_bare_runtime_error():
    """The --comm ZeRO legs skip (not fail) on a 1-ambient-device host:
    the gate raises a BARE RuntimeError — the same skippable class the
    graph-lint entry points use — which bench catches with an exact
    type check so real failures still propagate."""
    import pytest
    with pytest.raises(RuntimeError, match="no shard split") as ei:
        bench.require_shard_devices(1)
    assert type(ei.value) is RuntimeError      # bare, not a subclass
    # 2+ devices pass straight through
    bench.require_shard_devices(2)
    bench.require_shard_devices(8)
