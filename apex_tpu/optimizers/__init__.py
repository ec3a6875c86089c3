"""apex_tpu.optimizers — fused optimizers on flat parameter buffers.

Reference exports FusedAdam and FP16_Optimizer
(apex/optimizers/__init__.py:1-2); FusedLAMB is added here on top of the
reference's LAMB stage1/stage2 kernel semantics (SURVEY.md §2.2 gap).
"""

from .base import (Optimizer, SGD, SGDState, GradSegments, resolve_lr,
                   global_grad_norm)
from .fused_adam import FusedAdam, AdamState
from .fused_lamb import FusedLAMB, LambState
from .fused_lion import FusedLion, LionState
from .fp16_optimizer import FP16_Optimizer, FP16OptState
