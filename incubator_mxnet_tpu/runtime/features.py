"""Runtime feature detection (parity: python/mxnet/runtime.py —
`mx.runtime.Features()`, `is_enabled`, feature_list)."""
from __future__ import annotations

import jax

__all__ = ["Feature", "Features", "feature_list"]


class Feature:
    def __init__(self, name, enabled):
        self.name = name
        self.enabled = bool(enabled)

    def __repr__(self):
        return f"[{'✔' if self.enabled else '✖'} {self.name}]"


def _detect():
    from ..ops import pallas as _pallas
    backend = jax.default_backend()
    return {
        "TPU": _pallas.is_tpu(),
        "CPU": True,
        "CUDA": backend == "gpu",          # reference flag name; XLA:GPU here
        "BF16": True,                       # native MXU dtype
        "F16C": True,
        "PALLAS": _pallas.enabled(),        # custom TPU kernels
        "DIST_MESH": len(jax.devices()) > 1,  # multi-device collectives
        "OPENCV": False,
        "BLAS_OPEN": True,                  # XLA handles BLAS
        "SSE": True,
        "INT64_TENSOR_SIZE": True,
        "SIGNAL_HANDLER": False,
        "PROFILER": True,
    }


class Features(dict):
    """dict of name -> Feature with `is_enabled`, like the reference."""

    def __init__(self):
        super().__init__({k: Feature(k, v) for k, v in _detect().items()})

    def is_enabled(self, name):
        feat = self.get(name.upper())
        return bool(feat and feat.enabled)

    def __repr__(self):
        return "[" + ", ".join(repr(v) for v in self.values()) + "]"


def feature_list():
    return list(Features().values())
