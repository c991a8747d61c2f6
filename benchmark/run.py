#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json, in a process of its own:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1> [--rehearse]

The cell names a configuration (benchmark/configs/<config>.json and .py)
and a traffic mix (benchmark/traffic/<traffic>.json, whose `kind` names
the driver benchmark/traffic/<kind>.py). Each per-layer metric has a
reader, benchmark/layers/<metric>.py. This file knows none of them by
name: a new cell, configuration, traffic kind or metric is new files and
new entries in BENCHMARK.json.

The run needs a TPU: on any other platform it exits non-zero and prints
no result. `--rehearse` walks the same control flow at the toy sizes of
the files' `rehearse` blocks on whatever platform jax finds; its result
is never `correct` and it exits non-zero.

The last line of standard output is the result, one JSON object; what
else the run has to say comes on earlier lines.
"""
import time

T0 = time.perf_counter()    # set-up starts here, before the heavy imports

import argparse                     # noqa: E402
import contextlib                   # noqa: E402
import importlib.util               # noqa: E402
import json                         # noqa: E402
import os                           # noqa: E402
import shutil                       # noqa: E402
import statistics                   # noqa: E402
import sys                          # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for path in (ROOT, HERE):           # the package under test; lib/
    if path not in sys.path:
        sys.path.insert(0, path)


def load(kind, name):
    """benchmark/<kind>/<name>.py as a module."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def read_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def metrics_of(manifest, group, cell):
    """The cell's metrics of one group: those that list it, and those
    that list no cell at all."""
    return [m for m in manifest[group]
            if cell in m.get("workloads", [cell])]


def sized(doc, rehearse):
    """A configuration or traffic file as it is run: at its own sizes, or
    with its `rehearse` block laid over them."""
    doc = dict(doc)
    toy = doc.pop("rehearse", {})
    if rehearse:
        doc.update(toy)
    return doc


RESERVED_TOLERANCE = 0.05


def held_bytes(stats):
    """Peak device memory from `memory_stats()`. The TPU runtime keeps a
    program's temporaries in a region it RESERVES, apart from the buffers
    it counts as in use (limit - reserved - in use = the largest free
    block, to the megabyte): so what the chip held while the steps ran is
    the two together, unless an earlier moment of set-up held more."""
    return max(stats.get("peak_bytes_in_use", 0),
               stats.get("bytes_in_use", 0)
               + stats.get("peak_bytes_reserved", 0))


def reserved_is_filled(stats, program):
    """Whether the reserved region that `held_bytes` counts is the
    measured program's temporaries, by the compiler's own account
    (`memory_analysis()` of the compiled program, which the traffic driver
    gives as `program_bytes`), and the buffers in use hold at least its
    arguments. A pool that is reserved and not filled does not count as
    held: such a run is not `correct`. True where there is nothing to
    compare (no allocator statistics, as on XLA:CPU, or no program)."""
    if not program or "peak_bytes_reserved" not in stats:
        return True
    off = abs(stats["peak_bytes_reserved"] - program["temp"])
    return (off <= RESERVED_TOLERANCE * program["temp"]
            and stats["bytes_in_use"] >= program["argument"])


class Bench:
    """What a traffic driver is given, and what the readers read."""

    def __init__(self, args, cell, config, traffic, compile_log,
                 device_kind):
        self.cell = cell
        self.device_kind = device_kind
        self.config = config
        self.traffic = traffic
        # weights and data come from this; jax's keys hold 32 bits
        self.seed = args.seed % (2 ** 31 - 1)
        self.seconds = args.seconds
        self.trace = bool(args.trace)
        self.compile_log = compile_log
        self.trace_dir = os.path.join(ROOT, ".bench_trace", cell["name"])
        self.setup_s = None
        self.phases = {}            # seconds since the process started
        self.outcome = {}           # what the traffic driver returned
        self.trace_summary = None   # lib/xplane.reduce() of a traced run

    def note(self, **fields):
        """An earlier line of output."""
        print(json.dumps(fields), flush=True)

    def mark(self, phase):
        """A phase of set-up ended now (a finer account of `setup_s`)."""
        self.phases[phase] = time.perf_counter() - T0

    def open_window(self):
        """Set-up is over: the measured window starts now."""
        self.compile_log.open_window()
        self.setup_s = time.perf_counter() - T0

    def close_window(self):
        self.compile_log.close_window()

    @contextlib.contextmanager
    def tracing(self):
        """The profiler on around a part of the window (`--trace 1`)."""
        import jax
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # the benchmark's spans only
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        try:
            yield
        finally:
            jax.profiler.stop_trace()

    def median_ms(self, timer):
        values = self.outcome.get("timers", {}).get(timer)
        return statistics.median(values) * 1e3 if values else None


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes, any platform, never correct")
    args = ap.parse_args(argv)

    manifest = read_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if args.workload not in cells:
        sys.exit(f"no cell {args.workload!r}; BENCHMARK.json has "
                 f"{sorted(cells)}")
    cell = cells[args.workload]
    files = {c["name"]: c["file"] for c in manifest["configs"]}
    config_doc = sized(read_json(ROOT, files[cell["config"]]), args.rehearse)
    traffic_doc = sized(read_json(HERE, "traffic", cell["traffic"] + ".json"),
                        args.rehearse)
    if args.rehearse:
        args.seconds = min(args.seconds, 2.0)

    import jax
    marks = {"import_jax": time.perf_counter() - T0}
    devices = jax.devices()
    marks["devices"] = time.perf_counter() - T0
    platform, kind = devices[0].platform, devices[0].device_kind
    if platform != "tpu" and not args.rehearse:
        sys.exit(f"benchmark: needs a TPU; jax found platform {platform!r} "
                 f"({kind}). --rehearse walks the control flow here and is "
                 f"never correct.")
    if len(devices) < cell["chips"]:
        sys.exit(f"benchmark: {cell['name']} needs {cell['chips']} chips; "
                 f"jax found {len(devices)}")

    from incubator_mxnet_tpu.runtime import cache_guard
    from lib import compile_log, xplane

    bench = Bench(args, cell, config_doc, traffic_doc,
                  compile_log.CompileLog(), kind)
    bench.phases.update(marks)
    bench.mark("package")
    cache_dir = cache_guard.use_compile_cache(ROOT)
    # every program, however quickly it compiles: a cell's second run
    # finds them all in the cache, and set-up does not swing with the few
    # that compile in about the default threshold of half a second
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    bench.note(cell=cell["name"], seed=args.seed, seconds=args.seconds,
               trace=args.trace, rehearse=args.rehearse, platform=platform,
               kind=kind, count=len(devices), compile_cache=cache_dir)
    outcome = bench.outcome = load("traffic", traffic_doc["kind"]).run(
        bench, load("configs", cell["config"]))
    log = bench.compile_log
    bench.note(setup_s=bench.setup_s, setup_phases=bench.phases,
               setup=log.setup, closed=log.closed,
               compiles_in_window=log.in_window())

    used = jax.local_devices()[:cell["chips"]]
    stats = [d.memory_stats() or {} for d in used]      # None on XLA:CPU
    program = outcome.get("program_bytes")
    filled = all(reserved_is_filled(s, program) for s in stats)
    bench.note(memory_stats=stats[0], program_bytes=program,
               peak_bytes_in_use=stats[0].get("peak_bytes_in_use"),
               reserved_is_filled=filled)
    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": max(map(held_bytes, stats))}
    result = {"correct": bool(outcome["correct"]) and not args.rehearse
              and log.in_window() == 0 and filled,
              "attempted": outcome["attempted"], "failed": outcome["failed"]}
    if not bench.trace:
        values = dict(outcome["end_to_end"], setup_s=bench.setup_s)
        chosen = metrics_of(manifest, "end_to_end", cell["name"])
    else:
        try:
            bench.trace_summary = xplane.reduce(
                xplane.load(xplane.newest(bench.trace_dir)))
        except xplane.NoDevicePlane as e:
            if not args.rehearse:
                raise
            bench.note(no_device_trace=str(e))
        if bench.trace_summary:
            summary = bench.trace_summary
            bench.note(trace=summary)
            device.update(busy_s=summary["busy_s"],
                          window_s=summary["window_s"])
            result["breakdown"] = {"device_ops": summary["device_ops"],
                                   "idle_gaps": summary["idle_gaps"]}
        chosen = metrics_of(manifest, "per_layer", cell["name"])
        values = {m["name"]: load("layers", m["name"]).read(bench)
                  for m in chosen}
    result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                     "unit": m["unit"]}
                         for m in chosen if values.get(m["name"]) is not None}
    result["device"] = device
    print(json.dumps(result), flush=True)
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
