"""`mfu_pct.train`: the share of the WHOLE traced window's peak, fed a
fixed window, chip count and FLOPs. Nothing here is a device number."""
import importlib.util
import os
import sys
import types

import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "benchmark")
LAYERS = os.path.join(BENCH, "layers")
sys.path.insert(0, BENCH)       # the readers import `lib`, as run.py lets them


def _reader(metric):
    spec = importlib.util.spec_from_file_location(
        "bench_layer_" + metric.replace(".", "_"),
        os.path.join(LAYERS, metric + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def _bench(window_s, busy_s, chips, flops, kind="TPU v5 lite"):
    return types.SimpleNamespace(
        trace_summary={"window_s": window_s, "busy_s": busy_s,
                       "chips": chips},
        device_kind=kind, outcome={"traced_flops": flops})


@pytest.mark.parametrize("window_s,busy_s,chips,share", [
    (3.0, 3.0, 1, 0.2),       # never idle: equal to the busy-time share
    (4.0, 3.0, 1, 0.25),      # a quarter idle
    (3.345, 3.3427, 1, 0.1985),   # the GPT-2 cell's proportions
    (2.0, 0.5, 4, 0.9),       # four chips share the FLOPs
], ids=["busy", "quarter-idle", "gpt2-like", "four-chips"])
def test_mfu_is_the_share_of_the_whole_window(window_s, busy_s, chips, share):
    bench = _bench(window_s, busy_s, chips,
                   share * window_s * chips * 197e12)
    mfu = _reader("mfu_pct.train")(bench)
    assert mfu == pytest.approx(100.0 * share)
    # the identity PERF.md states: MFU = (100 - idle) x busy share / 100
    idle = _reader("device_idle_pct")(bench)
    busy = _reader("busy_flops_pct.train")(bench)
    assert mfu == pytest.approx((100.0 - idle) * busy / 100.0, abs=1e-9)
    assert mfu <= busy


@pytest.mark.parametrize("trace,outcome", [
    (None, {"traced_flops": 1e15}),
    ({"window_s": 1.0, "busy_s": 1.0, "chips": 1}, {}),
], ids=["no-trace", "no-flops"])
def test_mfu_reads_nothing_without_a_trace(trace, outcome):
    bench = types.SimpleNamespace(trace_summary=trace, device_kind="cpu",
                                  outcome=outcome)
    assert _reader("mfu_pct.train")(bench) is None


def test_mfu_needs_a_published_peak():
    with pytest.raises(KeyError):
        _reader("mfu_pct.train")(_bench(1.0, 1.0, 1, 1e12, kind="cpu"))
