"""Seconds of set-up spent tracing, lowering and loading every program but
the fused step (initialisers, the shape pass's eager ops, optimizer states,
the cache canary, batch making): the gauge `setup/other_programs_s`, whose
loads `setup/other_programs` counts (lib/setup_account.py)."""
from lib import setup_account


def read(bench):
    return setup_account.seconds("other_programs_s")
