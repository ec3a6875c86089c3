"""apex_tpu — TPU-native mixed-precision + data-parallel training toolkit.

A brand-new framework with the capabilities of NVIDIA Apex (reference:
/root/reference, apex/__init__.py:4-16), built idiomatically on JAX/XLA:

- ``apex_tpu.amp`` — automatic mixed precision: opt-levels O0-O3, dynamic
  loss scaling, op-level half/fp32 cast policies (reference: apex/amp).
- ``apex_tpu.parallel`` — DistributedDataParallel-style gradient psum over a
  device mesh, SyncBatchNorm with cross-chip Welford statistics, LARC,
  Reducer (reference: apex/parallel).
- ``apex_tpu.optimizers`` — FusedAdam / FusedLAMB / FP16_Optimizer backed by
  Pallas kernels over fused flat parameter buffers (reference:
  apex/optimizers + csrc/fused_adam_cuda*, csrc/multi_tensor_lamb*).
- ``apex_tpu.normalization`` — FusedLayerNorm (reference:
  apex/normalization/fused_layer_norm.py + csrc/layer_norm_cuda*).
- ``apex_tpu.fp16_utils`` — manual master-weight toolkit and the legacy
  FP16_Optimizer wrapper (reference: apex/fp16_utils).
- ``apex_tpu.nn`` — the minimal policy-aware layer library the amp machinery
  plugs into (the reference monkey-patches torch; we consult a dtype policy
  at op dispatch instead).
- ``apex_tpu.observability`` — unified telemetry: metrics registry with
  device-resident training-step counters, span tracing over the profiler
  ranges, and JSONL / Chrome-trace / Prometheus exporters (the reference
  ships only nvtx ranges and an AverageMeter).

Unlike the reference, every fused kernel has a pure-jnp fallback selected
automatically off-TPU, mirroring Apex's graceful-degradation invariant
(reference README.md:90-95).
"""

import time as _time
_import_began = _time.perf_counter()

from . import nn
from . import amp
from . import multi_tensor_apply
from . import optimizers
from . import normalization
from . import parallel
from . import fp16_utils
from . import RNN
from . import reparameterization
from . import transformer
from . import models
from . import utils
from . import observability
from . import data
from . import lora
from . import serving
from . import fleet
from . import analysis

__version__ = "0.1.0"

# what importing the package cost, as a span on the process recorder
observability.get_recorder().add_span("apex_tpu.import", _import_began,
                                      _time.perf_counter())
