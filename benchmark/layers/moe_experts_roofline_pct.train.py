"""Share of the roofline the held experts' grouped products reach: the
least time the chip could take for them, the larger of operations over the
bf16 peak and bytes over the HBM peak (the configuration's `expert_flops`
and `expert_bytes` on the rows the program counted as live, forward and the
two products of backward), over the device time under the op scopes
`moe/experts`, both phases. The same work whatever implements it."""
from lib import owned


def read(bench):
    ideal = bench.outcome.get("ideal_s_per_step")
    ms = owned.ms_per_step(bench, owned.under("moe", "experts"))
    if not ideal or not ms:
        return None
    return 100.0 * ideal["moe_experts"] / (ms / 1e3)
