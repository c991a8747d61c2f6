"""BAD fixture: every raw-read spelling of a MXTPU_* setting the
rule must catch (linted as if at incubator_mxnet_tpu/somemod.py)."""
import os
from os import getenv

a = os.environ.get("MXTPU_SOME_KNOB", "1")          # .get
b = os.getenv("MXTPU_GETENV_KNOB")                  # os.getenv
c = getenv("MXTPU_OTHER_KNOB")                      # bare getenv
d = os.environ["MXTPU_SUBSCRIPT_KNOB"]              # subscript read
e = "MXTPU_MEMBERSHIP_KNOB" in os.environ           # membership read


def helper(name):
    # dynamic-name wrapper: the drift vector the rule exists for
    return os.environ.get(name, "")
