"""Pallas TPU kernels for the hot ops (reference analogue: the hand-written
CUDA/cuDNN kernels under src/operator/contrib/ and src/operator/nn/).

On TPU these run as real Mosaic kernels; off-TPU they run with
``interpret=True`` (tests) or are bypassed in favor of the XLA path. A
kernel that does not compile raises where it is called — there is no
start-up self-test and no silent switch to the XLA path
(``python chip_smoke.py`` checks every kernel on the chip, and
tests/test_tpu_compile.py compiles them for a described v5e).
"""
from .flash_attention import flash_attention, latent_flash_attention
from .layer_norm import layer_norm
from .conv_bn_relu import conv_bn_relu, scale_shift_act, fold_bn
from .grouped_matmul import grouped_matmul

import jax

__all__ = ["flash_attention", "latent_flash_attention", "layer_norm",
           "conv_bn_relu", "scale_shift_act", "fold_bn", "grouped_matmul",
           "enabled", "is_tpu"]


def enabled() -> bool:
    """Use pallas kernels for framework ops? On by default on TPU.
    MXTPU_PALLAS is the master switch:
    ``0`` forces the plain XLA path everywhere (the escape hatch);
    ``1`` is explicit-on (off-TPU runs interpret-mode kernels);
    ``force`` selects kernels everywhere (what the CPU parity tests
    use). MXTPU_NO_PALLAS=1 / MXTPU_FORCE_PALLAS=1 are the legacy
    spellings and keep working.

    The three spellings resolve in ``settings.resolve("pallas")``
    (off > force > on > auto). Per-call-site qualification
    (shape/dtype/layout) lives in ops/select.py on top of this
    switch."""
    from ... import settings as _settings
    mode = _settings.resolve("pallas")[0]
    if mode == "off":
        return False
    if mode in ("force", "on"):
        return True
    return is_tpu()                           # auto


def is_tpu() -> bool:
    """True when jax's default backend is a TPU. The single definition of
    "on TPU" for kernel dispatch, interpret-mode selection, and runtime
    feature flags."""
    return jax.default_backend() == "tpu"
