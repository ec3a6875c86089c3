"""apex_tpu.parallel — data parallelism, SyncBatchNorm, LARC
(reference: apex/parallel/__init__.py).
"""

from typing import List, Optional, Tuple

from . import multiproc
from .topology import (make_mesh, mesh_info, hierarchical_axis_groups,
                       default_ici_size, auto_comm_topology,
                       overlap_issue_order, collective_rank_groups)
from .distributed import (DistributedDataParallel, Reducer,
                          allreduce_grads_tree, allreduce_comm_plan,
                          plan_collective_expectations,
                          plan_resharding_expectations,
                          zero_update_comm_plan,
                          predivide_factors, flat_dist_call,
                          staged_grads, overlap_comm_schedule,
                          overlap_collective_expectations, OVERLAP_MODES)
from .sync_batchnorm import SyncBatchNorm
from .LARC import LARC
from . import tensor_parallel
from .tensor_parallel import (ColumnParallelLinear, RowParallelLinear,
                              ParallelMLP, ParallelSelfAttention)
from . import pipeline
from . import expert_parallel
from .expert_parallel import ExpertParallelMLP


class ReduceOp:
    """Shim mirroring torch.distributed.ReduceOp (parallel/__init__.py:3-8)."""
    SUM = "psum"
    MAX = "pmax"
    MIN = "pmin"
    MEAN = "pmean"


def convert_syncbn_model(module, process_group=None, channel_last=False):
    """Recursively replace BatchNorm2d children with SyncBatchNorm,
    preserving hyperparameters (reference parallel/__init__.py:21-53).

    Because apex_tpu params live outside the module tree and SyncBatchNorm
    has the identical param/state schema, existing params trees stay valid
    — the stats-copy dance of the reference is unnecessary.  Returns the
    (mutated) module for call-shape parity.
    """
    from ..nn.layers import BatchNorm2d

    def maybe_convert(mod):
        if type(mod) is BatchNorm2d:
            new = SyncBatchNorm(
                mod.num_features, eps=mod.eps, momentum=mod.momentum,
                affine=mod.affine,
                track_running_stats=mod.track_running_stats,
                process_group=process_group, channel_last=channel_last,
                channel_axis=mod.channel_axis)
            return new
        return None

    converted = maybe_convert(module)
    if converted is not None:
        return converted
    stack = [module]
    while stack:
        mod = stack.pop()
        for name, child in list(mod.named_children()):
            new = maybe_convert(child)
            if new is not None:
                mod._replace_child(name, new)
            else:
                stack.append(child)
    return module


def create_syncbn_process_group(group_size: int,
                                world_size: Optional[int] = None,
                                axis_name: str = "data"
                                ) -> Tuple[str, List[List[int]]]:
    """Partition the axis into groups of ``group_size`` for grouped BN stat
    sync (reference parallel/__init__.py:55-92).  Returns a
    ``(axis_name, axis_index_groups)`` pair to pass as
    ``SyncBatchNorm(process_group=...)``; group 0 contains ranks
    [0, group_size), etc.
    """
    import jax
    if world_size is None:
        world_size = jax.device_count()
    if group_size == 0 or group_size >= world_size:
        return (axis_name, None)
    if world_size % group_size != 0:
        raise ValueError(
            f"world_size {world_size} must be divisible by group_size "
            f"{group_size}")
    groups = [list(range(i, i + group_size))
              for i in range(0, world_size, group_size)]
    return (axis_name, groups)
