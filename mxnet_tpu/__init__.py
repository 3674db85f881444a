"""mxnet_tpu — a TPU-native deep-learning framework with MXNet's capability
surface.

Brand-new design (NOT a port) targeting JAX/XLA/Pallas/pjit:

- ``mx.nd`` / ``mx.np``: imperative NDArray backed by jax.Array (PJRT HBM
  buffers); async semantics come from XLA dispatch, not a threaded engine.
- ``mx.autograd``: dynamic tape whose nodes are jax.vjp closures.
- ``mx.gluon``: Block/HybridBlock/Trainer; hybridize() traces the block into
  one jit-compiled XLA computation (the CachedOp equivalent).
- ``mx.kvstore`` + ``mxnet_tpu.parallel``: data/tensor/pipeline/sequence
  parallelism via jax.sharding Mesh + collectives over ICI.
- Hot ops as Pallas TPU kernels (mxnet_tpu/ops/pallas_*).

Reference capability map: SURVEY.md at the repo root (mozga-intel/
incubator-mxnet structural survey).
"""
from __future__ import annotations

__version__ = "2.0.0-tpu0"


def _maybe_init_distributed():
    """Join the process group when launched by tools/launch.py.

    The launcher exports MXNET_DIST_{COORDINATOR,NUM_WORKERS,RANK}; this
    replaces the ps-lite scheduler handshake (reference tools/launch.py +
    kvstore_dist.h rendezvous) with jax.distributed's coordination
    service.  Must run before the first jax backend initialization."""
    import os

    coord = os.environ.get("MXNET_DIST_COORDINATOR")
    if not coord:
        return
    import jax

    jax.distributed.initialize(
        coordinator_address=coord,
        num_processes=int(os.environ["MXNET_DIST_NUM_WORKERS"]),
        process_id=int(os.environ["MXNET_DIST_RANK"]))


_maybe_init_distributed()

from . import base, telemetry  # telemetry first: instrumented layers use it
from . import trace  # structured tracing + flight recorder (uses telemetry)
from . import autograd, context, engine
from . import ndarray
from . import ndarray as nd
from . import random
from .base import MXNetError, get_env
from .context import (Context, cpu, cpu_pinned, current_context, gpu,
                      num_gpus, num_tpus, tpu)
from .ndarray.ndarray import NDArray, waitall

# lazily-importable heavy submodules
from . import initializer  # noqa: E402
from . import optimizer  # noqa: E402
from . import gluon  # noqa: E402
from . import numpy as np  # noqa: E402
from . import numpy_extension as npx  # noqa: E402
from . import kvstore as kv  # noqa: E402
from . import kvstore  # noqa: E402
from . import io  # noqa: E402
from . import recordio  # noqa: E402
from . import symbol  # noqa: E402
from . import symbol as sym  # noqa: E402
from . import profiler  # noqa: E402
from . import runtime  # noqa: E402
from . import util  # noqa: E402
from . import parallel  # noqa: E402
from . import test_utils  # noqa: E402
from . import contrib  # noqa: E402
from . import metric  # noqa: E402  (alias of gluon.metric, reference layout)
from . import operator  # noqa: E402  (mx.operator CustomOp API)
from . import library  # noqa: E402  (extension .so loading)
from . import image  # noqa: E402
from . import checkpoint  # noqa: E402  (async/sharded/atomic persistence)
from . import serve  # noqa: E402  (dynamic-batching inference serving)
from . import compile  # noqa: E402,A004  (persistent compile cache + AOT)
from . import monitor  # noqa: E402  (training-health numerics + sentinel)
from . import resilience  # noqa: E402  (fault injection + preempt + supervisor)
from . import dist  # noqa: E402  (multi-host membership + pod checkpoints)
from . import obs  # noqa: E402  (fleet-wide observability plane)
from . import fleet  # noqa: E402  (multi-replica serving fleet)
from . import tenant  # noqa: E402  (multi-tenant serving: LoRA banks + WFQ)
from . import shard  # noqa: E402  (global mesh + ZeRO weight-update sharding)
from . import step  # noqa: E402  (whole-program training-step capture)
from . import data  # noqa: E402  (sharded streaming input pipeline)
from . import elastic  # noqa: E402  (failure detection + auto-resume)
from . import config  # noqa: E402  (env-var registry, reference env_var.md)
from . import subgraph  # noqa: E402  (SubgraphProperty partitioner hooks)
from . import callback  # noqa: E402  (Speedometer/checkpoint callbacks)
from . import dlpack  # noqa: E402  (DLPack interop)
from . import error  # noqa: E402  (structured error classes)
from . import visualization  # noqa: E402  (print_summary/plot_network)
from .optimizer import lr_scheduler  # noqa: E402  (mx.lr_scheduler)
from .dlpack import (from_dlpack, to_dlpack_for_read,  # noqa: E402
                     to_dlpack_for_write)

if base.get_env("MXNET_PROFILER_AUTOSTART", bool, False):
    profiler.set_state("run")  # reference env_var.md MXNET_PROFILER_AUTOSTART
from .util import is_np_array, set_np, reset_np, use_np  # noqa: E402
