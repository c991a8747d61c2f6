#!/usr/bin/env python3
"""What a cell's reference comparison has to refuse, through the comparison
itself (benchmark/traffic/train_steps_ref.py `control`): the plain
reference computed with lower-precision operands in every product, and the
float32 reference with one parameter's update left out, each held to the
float32 reference under the limits of the cell's traffic file.

    python3 tools/reference_control.py --workload <cell> --seed <n>
        [--operands float8_e4m3fn] [--unmoved 0] [--rehearse]

Prints, on the line `control`, each side's errors beside their limits and
`inside`, which has to read false for both: the readings a limit is set
against (PERF.md). No step is built or timed; on the chip the real sizes
take a few minutes, most of it the reference's compile.
"""
import argparse
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
for path in (ROOT, BENCH):
    if path not in sys.path:
        sys.path.insert(0, path)

import run  # noqa: E402  (benchmark/run.py)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--operands", default="float8_e4m3fn")
    ap.add_argument("--unmoved", type=int, default=0,
                    help="the trained parameter whose update is left out")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    manifest = run.read_json(ROOT, "BENCHMARK.json")
    cell = {w["name"]: w for w in manifest["workloads"]}[args.workload]
    files = {c["name"]: c["file"] for c in manifest["configs"]}
    traffic = run.sized(run.read_json(BENCH, "traffic",
                                      cell["traffic"] + ".json"),
                        args.rehearse)
    bench = run.Bench(
        types.SimpleNamespace(seed=args.seed, seconds=0, trace=0), cell,
        run.sized(run.read_json(ROOT, files[cell["config"]]), args.rehearse),
        traffic, None, None)
    sides = run.load("traffic", traffic["kind"]).control(
        bench, run.load("configs", cell["config"]), args.operands,
        args.unmoved)
    kind = run.load("traffic", traffic["kind"])
    return 1 if any(kind.inside(v) for v in sides.values()) else 0


if __name__ == "__main__":
    sys.exit(main())
