"""GOOD fixture: reads routed through settings.py, allowlisted
arming knobs, non-knob env reads, and env WRITES (config, not reads)."""
import os


def resolved():
    from incubator_mxnet_tpu.settings import env_int, env_str
    return env_int("MXTPU_SOME_KNOB", 1), env_str("MXTPU_OTHER_KNOB")


def non_knob():
    # not a MXTPU_* name: out of the rule's jurisdiction
    return os.environ.get("JAX_PLATFORMS", "")


def write_is_config():
    os.environ["MXTPU_SOME_KNOB"] = "1"          # a write, not a read
