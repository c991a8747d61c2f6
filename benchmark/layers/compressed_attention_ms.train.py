"""Device time a step, forward and backward, of everything under the op
scope `compressed_attention` (ops/_raw.py `compressed_attention`: the value
shift, the two causal convolutions, the q-k mean, the unit norms, the
partial rotation and the attention kernels of every CCA mixer; the four
projections around it are the blocks' `dense` and NOT in it; lib/owned.py;
the names are the program's: docs/profiler.md, "Names in a device trace").
Its kernels' share is `attention_fwd_ms.train` + `attention_bwd_ms.train`;
the rest is what the latent costs beside them."""
from lib import owned


def read(bench):
    return owned.ms_per_step(bench, owned.under("compressed_attention"))
