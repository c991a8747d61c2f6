"""Self time of the span `mxtpu.setup.shapes`: the eager forward of
`FusedTrainStep._resolve` that completes deferred shapes, less the weights it
makes (`init_s.setup`) and the programs it compiles (`eager_programs_s.setup`);
0 where no parameter is deferred. The gauge `setup/shapes_s`
(lib/setup_account.py)."""
from lib import setup_account


def read(bench):
    return setup_account.seconds("shapes_s")
