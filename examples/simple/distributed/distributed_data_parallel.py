"""Minimal DDP walkthrough — apex_tpu clone of the reference's
examples/simple/distributed/distributed_data_parallel.py (a ~40-line
script showing the DDP wrapper in isolation: tiny model, allreduced
grads, identical params on every rank).

Run it two ways:

single process over every device of the host — rehearse on a 4-device
CPU mesh (collectives over the mesh axis):
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
  python examples/simple/distributed/distributed_data_parallel.py
on a TPU host the same command without the two variables (one process
drives all its chips; `python chip_smoke.py` first).

multi-process (one process per "host", jax.distributed over localhost —
the analogue of the reference's torch.distributed.launch run; CPU only:
a chip belongs to one process, so the launcher refuses N local children
on an accelerator):
  python -m apex_tpu.parallel.multiproc --nprocs 2 --backend cpu \
  examples/simple/distributed/distributed_data_parallel.py
"""

import os
import sys

import numpy as np

_repo = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                     "..", "..", ".."))
if os.path.isdir(os.path.join(_repo, "apex_tpu")) and _repo not in sys.path:
    sys.path.insert(0, _repo)

from apex_tpu.parallel import multiproc

rank = multiproc.init_process_group()

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu import nn, optimizers, parallel
from apex_tpu.nn import functional as F
from apex_tpu.utils import configure_compile_cache

configure_compile_cache()
ndev = len(jax.devices())
model = nn.Sequential([nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4)])
params, _ = model.init(jax.random.PRNGKey(0))  # same seed => same init
opt = optimizers.SGD(lr=0.1)
opt_state = opt.init(params)
ddp = parallel.DistributedDataParallel(model)

mesh = Mesh(np.array(jax.devices()), ("data",))


def step(params, opt_state, x, y):
    def loss_fn(p):
        out = model(p, x)
        return F.mse_loss(out, y)

    loss, grads = jax.value_and_grad(loss_fn)(params)
    grads = ddp.allreduce_grads(grads)      # the one DDP line
    params, opt_state = opt.update(grads, opt_state, params)
    return params, opt_state, jax.lax.pmean(loss, "data")


train = jax.jit(jax.shard_map(
    step, mesh=mesh,
    in_specs=(P(), P(), P("data"), P("data")),
    out_specs=(P(), P(), P()), check_vma=False))

rng = np.random.RandomState(0)
x = jnp.asarray(rng.randn(4 * ndev, 8), jnp.float32)
y = jnp.asarray(rng.randn(4 * ndev, 4), jnp.float32)

for i in range(5):
    params, opt_state, loss = train(params, opt_state, x, y)
    if jax.process_index() == 0:
        print(f"step {i}: loss {float(loss):.6f}")

# every device must hold identical params after allreduced updates
leaves = jax.tree_util.tree_leaves(params)
for leaf in leaves:
    shards = [np.asarray(s.data) for s in leaf.addressable_shards]
    for s in shards[1:]:
        np.testing.assert_array_equal(shards[0], s)
if jax.process_index() == 0:
    print(f"OK: params identical across {ndev} devices "
          f"({jax.process_count()} processes)")
