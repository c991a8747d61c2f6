"""incubator_mxnet_tpu — a TPU-native deep learning framework with the
capabilities of Apache MXNet (reference: ymjiang/incubator-mxnet), rebuilt
from scratch on JAX/XLA/Pallas.

Import surface mirrors `mxnet`:

    import incubator_mxnet_tpu as mx        # or: import mxtpu as mx
    x = mx.nd.ones((2, 3), ctx=mx.tpu(0))
    with mx.autograd.record():
        y = (x * 2).sum()
    y.backward()
"""
import sys as _sys

from . import base, context
from .context import Context, cpu, gpu, tpu, current_context, num_gpus, num_tpus
from . import profiler
from . import ndarray
from . import ndarray as nd
from . import autograd
from . import ops
from . import initializer
from . import initializer as init
from . import optimizer
from .optimizer import lr_scheduler
from . import kvstore
from . import kvstore as kv
from . import gluon
from . import symbol
from . import symbol as sym
from .symbol import AttrScope
from .symbol import executor
from . import attribute
from . import contrib
from . import registry
from . import util
from . import rnn
from . import module
from . import module as mod
from . import model
from . import metric
from . import io
from . import operator
from . import callback
from . import monitor
from .monitor import Monitor
from . import visualization
from . import visualization as viz
from . import distributed
from . import recordio
from . import image
from . import amp
from . import runtime
from . import engine
from . import diagnostics
from . import healthmon
from . import perfscope
from . import commscope
from . import devicescope
from . import memscope
from . import servescope
from . import serving
from . import resilience
from . import mxlint
from . import embedding
from . import trainloop
from .trainloop import TrainLoop
from . import test_utils
from . import utils

from .ndarray import NDArray
from .ndarray import random as _ndrandom

# `mx.random` surface (seed + samplers)
random = _ndrandom

__version__ = "0.1.0"

# Short import alias, torch-style: `import mxtpu as mx`.
_sys.modules.setdefault("mxtpu", _sys.modules[__name__])

# MXTPU_DIAG=1: arm the always-on observability layer (memory ledger,
# flight recorder, optional sampler — see docs/diagnostics.md) at import.
diagnostics.enable_from_env()
# MXTPU_HEALTHMON=1: arm cross-rank training health (watchdogs, skew
# timeline, structured event log — see docs/observability.md) at import.
healthmon.enable_from_env()
# MXTPU_PERFSCOPE=1: arm roofline-aware cost capture at compile sites
# (per-program FLOPs/bytes + verdicts — see docs/perfscope.md) at import.
perfscope.enable_from_env()
# MXTPU_COMMSCOPE=1: arm collective/resharding extraction at the same
# compile sites (per-program inventory + estimates — docs/commscope.md).
commscope.enable_from_env()
# MXTPU_DEVICESCOPE=1: arm measured device-timeline capture (windowed
# jax-profiler trace + ingestion + analytic-vs-measured reconciliation
# — see docs/devicescope.md).
devicescope.enable_from_env()
# MXTPU_MEMSCOPE=1: arm memory observability (static per-program
# footprints at the compile sites, the watermark ring at the step
# marks, OOM forensics — see docs/memscope.md).
memscope.enable_from_env()
# MXTPU_SERVESCOPE=1: arm request-lifecycle tracing + tail-latency
# attribution on the serving path (sampled via MXTPU_SERVESCOPE_SAMPLE
# — see docs/servescope.md).
servescope.enable_from_env()
# MXTPU_STRICT=1: arm the mxlint strict-mode jit-program auditor
# (host-sync / recompile-storm / donation-violation detection over the
# steady loop — see docs/mxlint.md).
mxlint.runtime.enable_from_env()
