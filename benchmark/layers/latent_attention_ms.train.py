"""Device time a step, forward and backward, of everything under the op
scope `latent_attention` (models/moe_lm.py `LatentAttentionCell`: the
query's rank and its norm, `q_down` and `q_up`, the key/value down and up
projections with the latent's norm, the rotations under `rope`, the keys'
assembly and the flash kernels under `attention`, of every latent layer and
of the MTP block's; the output projection is the cell's `dense` and NOT in
it; lib/owned.py; the names are the program's: docs/profiler.md, "Names in
a device trace"). Its kernels' share is `attention_fwd_ms.train` +
`attention_bwd_ms.train`."""
from lib import owned


def read(bench):
    return owned.ms_per_step(bench, owned.under("latent_attention"))
