"""Seconds of set-up in which the fused step's program was loaded: the gauge
`setup/step_load_s`, jax's `backend_compile_duration` of `train_step` (the
cache key, the persistent cache's read and deserialisation, or a fresh
compile on a miss; lib/setup_account.py)."""
from lib import setup_account


def read(bench):
    return setup_account.seconds("step_load_s")
