"""Multi-process launcher + process-group bootstrap.

The reference ships a legacy one-process-per-GPU spawner
(apex/parallel/multiproc.py:12-35: read WORLD_SIZE, fork
``main.py --rank i`` per device, wait on children).  The TPU-native
equivalent is one process per *host*, wired together with
``jax.distributed.initialize`` so XLA collectives span hosts over DCN and
every process sees the global device set.

Two pieces:

- ``init_process_group()`` — called by the *trainee* script; reads the
  env wiring (ours or the standard JAX_* names) and brings up the
  distributed runtime. On a single process it is a no-op, mirroring the
  reference's world_size==1 passthrough paths.
- ``python -m apex_tpu.parallel.multiproc [--nprocs N] script.py args...``
  — the *launcher*: spawns N local processes with the wiring set, streams
  their output, and exits non-zero if any child fails (killing the
  survivors, which would otherwise block in distributed init). With
  ``--backend cpu`` each child runs on host-platform devices, giving a
  real multi-process collective runtime on one machine — the analogue of
  the reference's single-node ``torch.distributed.launch
  --nproc_per_node=2`` test setup
  (tests/L1/cross_product_distributed/run.sh).  The default ``auto``
  inherits the environment's platform, and every child runs on this
  host: an accelerator belongs to one process, so with more than one
  child the launcher refuses unless the platform is the CPU.
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys
from typing import Optional

ENV_RANK = "APEX_TPU_RANK"
ENV_WORLD = "APEX_TPU_WORLD_SIZE"
ENV_COORD = "APEX_TPU_COORDINATOR"


def init_process_group(coordinator_address: Optional[str] = None,
                       num_processes: Optional[int] = None,
                       process_id: Optional[int] = None) -> int:
    """Bring up ``jax.distributed`` from explicit args or env wiring.

    Returns the process id (rank). No-op (rank 0) when unwired, so scripts
    run unmodified both standalone and under the launcher.
    """
    coordinator_address = coordinator_address or os.environ.get(ENV_COORD)
    if num_processes is None:
        num_processes = int(os.environ.get(ENV_WORLD, "1"))
    if process_id is None:
        process_id = int(os.environ.get(ENV_RANK, "0"))
    if num_processes <= 1 or coordinator_address is None:
        return 0
    import jax
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)
    return process_id


def _probe_free_port() -> int:
    """Ask the kernel for a free TCP port for the coordinator.  The
    reference's launcher hardcodes 29500 (and so did round 1 here,
    parallel/multiproc.py:72) — two concurrent groups on one host then
    collide; an OS-assigned ephemeral port cannot."""
    import socket
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m apex_tpu.parallel.multiproc",
        description="spawn N local processes wired into one jax.distributed "
                    "process group")
    p.add_argument("--nprocs", type=int,
                   default=int(os.environ.get("WORLD_SIZE", "2")))
    p.add_argument("--port", type=int, default=0,
                   help="coordinator port; 0 probes for a free one "
                        "(default; a fixed 29500 collides with any other "
                        "group on the host)")
    p.add_argument("--backend", choices=["auto", "cpu"], default="auto",
                   help="cpu forces host-platform devices in the children")
    p.add_argument("--devices-per-proc", type=int, default=1,
                   help="host-platform device count per child (cpu backend)")
    p.add_argument("script")
    p.add_argument("script_args", nargs=argparse.REMAINDER)
    args = p.parse_args(argv)

    port = args.port or _probe_free_port()
    coord = f"127.0.0.1:{port}"
    if (args.backend == "auto" and args.nprocs > 1
            and os.environ.get("JAX_PLATFORMS", "") != "cpu"):
        # --backend auto inherits the environment's platform, and every
        # child runs on THIS host: an accelerator belongs to one process,
        # so the second child would fail or hang at start-up
        print(f"[multiproc] refusing to start {args.nprocs} children on "
              "one host with --backend auto: they would contend for its "
              "accelerator.  Pass --backend cpu for a local multi-process "
              "run; on a TPU host one process drives all its chips.",
              file=sys.stderr)
        return 2
    children = []
    for rank in range(args.nprocs):
        env = dict(os.environ)
        env[ENV_RANK] = str(rank)
        env[ENV_WORLD] = str(args.nprocs)
        env[ENV_COORD] = coord
        # reference-compatible names so unmodified scripts can read them
        env["RANK"] = str(rank)
        env["WORLD_SIZE"] = str(args.nprocs)
        if args.backend == "cpu":
            env["JAX_PLATFORMS"] = "cpu"
            # REPLACE any inherited device-count flag: a parent test
            # process runs on an 8-device virtual mesh, and inheriting
            # that would give each child 8 devices instead of
            # devices_per_proc (world 16, not nprocs)
            flags = [f for f in env.get("XLA_FLAGS", "").split()
                     if "xla_force_host_platform_device_count" not in f]
            flags.append("--xla_force_host_platform_device_count="
                         f"{args.devices_per_proc}")
            env["XLA_FLAGS"] = " ".join(flags)
        children.append(subprocess.Popen(
            [sys.executable, args.script, *args.script_args], env=env))

    # wait on children like the reference's final loop, but poll so one
    # crashed rank kills the others instead of deadlocking the group
    # (a failed rank leaves the survivors blocked in distributed init)
    import time
    rc = 0
    try:
        while True:
            codes = [c.poll() for c in children]
            failed = [code for code in codes if code not in (None, 0)]
            if failed:
                rc = failed[0]
                break
            if all(code is not None for code in codes):
                break
            time.sleep(0.2)
    finally:
        for c in children:
            if c.poll() is None:
                c.kill()
                c.wait()
    return rc


if __name__ == "__main__":
    sys.exit(main())
