#!/usr/bin/env python3
"""Third rehearsal of the on-chip guide: compile a cell's main program at its
real size for a described (not attached) ``v5e:2x2`` and print the planned
bytes per chip, so that the batch is fixed before chip time is spent.

    JAX_PLATFORMS=cpu python benchmark/tools/rehearse_v5e.py bert-large 8 12 --chips 1

A scratch script: it steers the program from outside (kernel dispatch told
the target is a TPU, ``jax.devices`` answering with the described chips,
``device_put`` leaving arrays where they are) and nothing runs.  A compile
that passes here is not a chip run.  The topology call is made only under
``__main__``.
"""

import argparse
import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, _ROOT)
sys.path.insert(0, os.path.join(_ROOT, "benchmark"))


def _plan(compiled):
    ma = compiled.memory_analysis()
    peak = (ma.argument_size_in_bytes + ma.output_size_in_bytes + ma.temp_size_in_bytes
            + ma.generated_code_size_in_bytes - ma.alias_size_in_bytes)
    return {"argument": ma.argument_size_in_bytes, "output": ma.output_size_in_bytes,
            "temp": ma.temp_size_in_bytes, "alias": ma.alias_size_in_bytes, "peak": peak}


def _abstract(tree, sharding):
    import jax
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding), tree)


def rehearse_train(config, sizes, chips, topo, seq_len):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from runners import train_example

    devices = list(topo.devices)[:chips]
    real_devices, real_put = jax.devices, jax.device_put
    for batch in sizes:
        jax.devices = lambda *a, **k: devices
        jax.device_put = lambda x, *a, **k: x
        try:
            cfg = dict(config, per_chip_batch=batch, seq_len=seq_len)
            run = train_example.build_example(cfg, _ROOT)
            state = jax.eval_shape(lambda: run.state)
        finally:
            jax.devices, jax.device_put = real_devices, real_put
        rep = NamedSharding(run.mesh, P())
        split = NamedSharding(run.mesh, P("data"))
        T = cfg["seq_len"]
        import numpy as np
        b = tuple(jax.ShapeDtypeStruct((batch * chips,) + s, np.int32, sharding=split)
                  for s in ((T,), (T,), ()))
        try:
            compiled = run.train_step.lower(_abstract(state, rep), b).compile()
        except jax.errors.JaxRuntimeError as e:        # what the chip's compiler would refuse
            print(json.dumps({"config": config["name"], "chips": chips, "per_chip_batch": batch,
                              "refused": str(e).split("\n")[0][:300]}), flush=True)
            continue
        text = compiled.as_text()
        print(json.dumps({"config": config["name"], "chips": chips, "per_chip_batch": batch,
                          "planned": _plan(compiled),
                          "tpu_custom_calls": text.count('"tpu_custom_call"'),
                          "all_reduce": text.count("all-reduce(")}), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("config")
    ap.add_argument("sizes", type=int, nargs="+", help="per-chip batches")
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--seq-len", type=int, default=512, help="of the training mix")
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    from jax.experimental import topologies
    from apex_tpu.ops import dispatch
    dispatch.backend = lambda: "tpu"       # compile the kernels the chip would run
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    with open(os.path.join(_ROOT, "benchmark", "configs", args.config + ".json")) as f:
        config = json.load(f)
    rehearse_train(config, args.sizes, args.chips, topo, args.seq_len)


if __name__ == "__main__":
    main()
