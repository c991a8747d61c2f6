"""Seconds of set-up in which jax traced and lowered the fused step's program
(`train_step`): the gauges `setup/step_trace_s` + `setup/step_lower_s`, the
union of jax's `jaxpr_trace_duration` and `jaxpr_to_mlir_module_duration`
spans of that function (lib/setup_account.py)."""
from lib import setup_account


def read(bench):
    return setup_account.seconds("step_trace_s", "step_lower_s")
