"""Self time of the span `mxtpu.setup.init` (Block.initialize,
ParameterDict.initialize, Parameter.finish_deferred_init): making the
weights, less the programs compiled inside it, which count in
`eager_programs_s.setup`. The gauge `setup/init_s` (lib/setup_account.py)."""
from lib import setup_account


def read(bench):
    return setup_account.seconds("init_s")
