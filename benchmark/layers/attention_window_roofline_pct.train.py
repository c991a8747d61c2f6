"""Share of the bf16 peak the flash-attention kernels reach on the pairs a
query may SEE: the configuration's `attention_flops` (forward 4 P d a head,
backward twice that; P counts the causal pairs, cut to the window on a
sliding layer) over 197e12 x the device time of the `flash_attention_*`
kernels. A window that is a mask and not a bound reads four times low on
the sliding layers. The kernels' ms by block go out on the earlier line
`attention_kernels_ms`, so a sliding layer can be held against a full one."""
import re

from lib import owned

KERNEL = "flash_attention_"


def read(bench):
    ideal = bench.outcome.get("ideal_s_per_step")
    found = owned.events(bench)
    if not ideal or not found:
        return None
    every, steps = found
    by_block = {}
    for name, parts, phase, ns in every:
        if KERNEL in name:
            block = next((p for p in parts if re.search(r"cell_\d+\Z", p)),
                         "?")
            by_block[block] = by_block.get(block, 0.0) + ns / 1e6 / steps
    if not by_block:
        return None
    bench.note(attention_kernels_ms=by_block)
    return 100.0 * ideal["attention"] / (sum(by_block.values()) / 1e3)
