#!/usr/bin/env python3
"""What the host was doing while a benchmark run lost time, sampled from
OUTSIDE the run's process (no jax, no shared GIL):

    python3 tools/host_sidecar.py out.jsonl &    # before benchmark/run.py
    ... the run ...;  kill %1

Every 50 ms one JSON line: `t` on `time.perf_counter()`'s clock (the
machine's monotonic clock, which run.py shares: the traffic kind
`train_steps_ref` prints its longest interval's end as `clock_s`) and
`gap`, the seconds since the sample before. A gap far over 0.05 s means
this loop was not run either: the whole machine paused, not the
benchmark. Every fourth line adds the benchmark process's threads: how
many in each scheduler state, and those that used CPU since the last look
(`busy`: [thread name, clock ticks]), which tells a main thread that waits
from one that works. PERF.md section 7 has what PR 28 read from it.
"""
import json
import os
import signal
import sys
import time


def read(path):
    try:
        with open(path) as f:
            return f.read()
    except OSError:
        return ""


def benchmark_pid():
    for pid in filter(str.isdigit, os.listdir("/proc")):
        # an argument of its own: a shell's command line holds the name too
        if any(arg.endswith("benchmark/run.py")
               for arg in read(f"/proc/{pid}/cmdline").split("\0")):
            return int(pid)
    return None


def threads(pid, ticks_before):
    """({state: count}, [[thread name, ticks used since the last look]])."""
    states, busy = {}, []
    for tid in os.listdir(f"/proc/{pid}/task"):
        stat = read(f"/proc/{pid}/task/{tid}/stat")
        if not stat:
            continue
        name = stat[stat.index("(") + 1:stat.rindex(")")]
        fields = stat[stat.rindex(")") + 2:].split()
        states[fields[0]] = states.get(fields[0], 0) + 1
        ticks = int(fields[11]) + int(fields[12])      # utime + stime
        if ticks > ticks_before.get(tid, ticks):
            busy.append([name, ticks - ticks_before[tid]])
        ticks_before[tid] = ticks
    return states, sorted(busy, key=lambda b: -b[1])[:8]


def main():
    stop = []
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, lambda *_: stop.append(True))
    pid, ticks_before, last = None, {}, time.perf_counter()
    with open(sys.argv[1], "w") as out:
        n = 0
        while not stop:
            time.sleep(0.05)
            now = time.perf_counter()
            row = {"t": round(now, 4), "gap": round(now - last, 4)}
            last, n = now, n + 1
            if n % 4 == 0:
                if pid is None or not os.path.exists(f"/proc/{pid}"):
                    pid, ticks_before = benchmark_pid(), {}
                if pid:
                    try:
                        row["threads"], row["busy"] = threads(pid,
                                                              ticks_before)
                    except OSError:     # the process ended under the scan
                        pid = None
            out.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
