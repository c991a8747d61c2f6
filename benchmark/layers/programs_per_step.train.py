"""Programs the device executed per `mxtpu.step` span: events of the device
plane's `XLA Modules` line in the traced part / steps. What the DEVICE sees
of one `FusedTrainStep.__call__`; the gauge `trainer.dispatches_per_step`
counts calls of the jitted step on the host (lib/scopes.py)."""
from lib import scopes


def read(bench):
    scoped = scopes.of(bench)
    return scoped and scoped["programs_per_step"]
