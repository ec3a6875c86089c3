#!/usr/bin/env python3
"""``rehearse_v5e.py`` for a decoder cell built through
``examples/gpt/main_amp.py``'s ``build()``: compile the cell's training step at
its real size for a described (not attached) ``v5e:2x2`` and print the planned
bytes of one chip, before chip time is spent.

    JAX_PLATFORMS=cpu python benchmark/tools/rehearse_lm_v5e.py ouro-2.6b \\
        --set remat=nothing --set head_chunk=4096 [--text-out <file>]

``--set key=value`` (JSON values) states a key of the configuration file
otherwise than the file does, for the plans a file lists under
``assumed.planned_bytes``; ``--text-out`` keeps the compiled text (what the
step's loops carry, which kernels it holds).  A scratch script as its twin is:
it steers the program from outside (kernel dispatch told the target is a TPU,
``jax.devices`` answering with the described chips, ``device_put`` leaving
arrays where they are) and nothing runs.  A compile that passes here is not a
chip run.  The topology call is made only under ``__main__``.
"""

import argparse
import json
import os
import sys
import tempfile

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, _ROOT)
sys.path.insert(0, os.path.join(_ROOT, "benchmark"))

from rehearse_v5e import _abstract, _plan  # noqa: E402


def rehearse(config: dict, topo, seq_len: int, text_out=None) -> dict:
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from runners import train_example

    devices = list(topo.devices)[:1]
    mod = train_example.load_example(_ROOT, config["example"])
    with tempfile.NamedTemporaryFile("w", suffix=".json") as f:
        json.dump(config, f)
        f.flush()
        argv = list(config["argv"]) + ["--model-config", f.name, "-b",
                                       str(config["per_chip_batch"]), "--seq-len", str(seq_len)]
        real_devices, real_put = jax.devices, jax.device_put
        jax.devices = lambda *a, **k: devices
        jax.device_put = lambda x, *a, **k: x
        try:
            run = mod.build(mod.parse_args(argv))
            state = jax.eval_shape(lambda: run.state)
        finally:
            jax.devices, jax.device_put = real_devices, real_put
    rep, split = NamedSharding(run.mesh, P()), NamedSharding(run.mesh, P("data"))
    ids = jax.ShapeDtypeStruct((config["per_chip_batch"], seq_len), np.int32, sharding=split)
    try:
        compiled = run.train_step.lower(_abstract(state, rep), (ids,)).compile()
    except jax.errors.JaxRuntimeError as e:            # what the chip's compiler would refuse
        return {"refused": str(e).split("\n")[0][:300]}
    text = compiled.as_text()
    if text_out:
        with open(text_out, "w") as f:
            f.write(text)
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(state[0]))
    return {"planned": _plan(compiled), "parameters": n,
            "tpu_custom_calls": text.count('"tpu_custom_call"'), "while": text.count(" while(")}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("config")
    ap.add_argument("--seq-len", type=int, default=8192, help="of the training mix")
    ap.add_argument("--set", action="append", default=[], metavar="KEY=JSON")
    ap.add_argument("--text-out", default=None)
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
    stated = {k: json.loads(v) for k, v in (s.split("=", 1) for s in args.set)}
    config.update(stated)
    print(json.dumps({"config": config["name"], "stated": stated,
                      **rehearse(config, topo, args.seq_len, args.text_out)}), flush=True)


if __name__ == "__main__":
    main()
