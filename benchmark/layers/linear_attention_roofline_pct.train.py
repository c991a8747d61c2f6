"""Share of the roofline the gated delta rule's scan reaches: the least
time the chip could take for the RECURRENCE of every linear-attention
layer, the larger of operations over the bf16 peak and bytes over the HBM
peak (the configuration's `linear_attention_flops` and
`linear_attention_bytes`, which count the token-by-token rule and not a
chunking; forward and the two passes of backward), over the device time
under the op scopes `linear_attention/scan`, both phases. The same work
whatever implements it. The configuration's module is loaded here:
`train_steps_ref.ideal_seconds` knows the two kernels it was written for."""
import importlib.util
import os

from lib import owned, peaks


def _config(name):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        os.pardir, "configs", name + ".py")
    spec = importlib.util.spec_from_file_location("bench_roofline_config",
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def ideal_seconds(bench):
    """Seconds a step the recurrences cannot go below on this chip; None
    where the configuration has no such layer, counts no such kernel, or
    the device's peaks are not published."""
    doc, traffic = bench.config, bench.traffic
    layers = doc.get("layer_types", [])[:doc.get("num_hidden_layers", 0)]
    count = layers.count("linear_attention")
    config = _config(bench.cell["config"])
    if not count or not hasattr(config, "linear_attention_flops"):
        return None
    try:
        flops = peaks.peak(bench.device_kind, "bf16_flops")
        bandwidth = peaks.peak(bench.device_kind, "hbm_bytes_per_s")
    except KeyError:
        return None
    seq = traffic["seq"]
    return 3 * traffic["batch"] * count * max(
        config.linear_attention_flops(doc, seq) / flops,
        config.linear_attention_bytes(doc, seq) / bandwidth)


def read(bench):
    ms = owned.ms_per_step(bench, owned.under("linear_attention", "scan"))
    if not ms:
        return None
    ideal = ideal_seconds(bench)
    return ideal and 100.0 * ideal / (ms / 1e3)
