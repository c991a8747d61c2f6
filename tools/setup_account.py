#!/usr/bin/env python3
"""One run of a benchmark cell, then the program's account of its set-up:

    python3 tools/setup_account.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1> [--rehearse]

Runs benchmark/run.py in this process with the same arguments (its lines
and exit code unchanged), then prints one more line,
{"setup_account": {...}}: every `setup/*` gauge and the lifetime
`compile/*` counters of `profiler.counters()`
(incubator_mxnet_tpu/profiler/build.py). The benchmark's own result line
is then the last but one. With `setup_s` and `setup_phases` from the run's
earlier line, the gauges split set-up as PERF.md section 5 does: the
package's import, init, shapes, states, the step's trace, lower and load,
the other programs, build's own dispatch, and what none of them names.
"""
import json
import os
import runpy
import sys

RUN = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmark", "run.py")


def main():
    sys.argv = [RUN] + sys.argv[1:]
    try:
        runpy.run_path(RUN, run_name="__main__")
        code = 0
    except SystemExit as e:
        code = e.code
    from incubator_mxnet_tpu import profiler
    account = {k: v for k, v in profiler.counters().items()
               if k.startswith(("setup/", "compile/"))}
    print(json.dumps({"setup_account": account}), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
