"""Device time a step, forward and backward, of everything under the
multi-token-prediction module's block scope (models/moe_lm.py `MTP`,
named `mtp_<n>`: its two norms, `eh_proj`, the embedding's second lookup,
its block, its head norm and the shared head's second product; lib/owned.py;
docs/profiler.md, "Names in a device trace"). It overlaps
`latent_attention_ms.train` and `moe_ms.train` by the MTP block's share; the
MTP's cross-entropy is under `loss` (`cross_entropy_ms.train`)."""
from lib import owned, scopes


def _in_mtp(name, parts, phase):
    return "mtp" in scopes.owner_class("/".join(parts)).split("/")


def read(bench):
    return owned.ms_per_step(bench, _in_mtp)
