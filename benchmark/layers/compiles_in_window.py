"""Backend compile requests inside the measured window (jax.monitoring).
Anything but 0 also makes the run not `correct`."""


def read(bench):
    return bench.compile_log.in_window()
