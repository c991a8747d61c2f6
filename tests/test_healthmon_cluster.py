"""REAL 2-process cluster healthmon acceptance (not mocks): runs
tools/health_cluster.py, which forms a loopback gloo cluster with an
injected slow rank (sleep on rank 1) and an injected NaN loss (rank 0),
and asserts the cross-rank contract — skew metric with slowest-rank
attribution on every rank, NaN watchdog alert within one step, and a
validated `mxdiag merge` timeline spanning both ranks.

The driver is tools/health_cluster.py; this test only asserts the
driver's verdict (and keeps its artifacts out of /tmp's shared path).
"""
import json
import os
import subprocess
import sys

import pytest

_HERE = os.path.dirname(os.path.abspath(__file__))
_DRIVER = os.path.join(os.path.dirname(_HERE), "tools",
                       "health_cluster.py")

_TIMEOUT_S = int(os.environ.get("MXTPU_TEST_WORKER_TIMEOUT", "420"))


@pytest.mark.serial
def test_two_process_straggler_and_nan_detection(tmp_path):
    env = dict(os.environ)
    env["MXTPU_HM_OUT"] = str(tmp_path / "cluster")
    env["MXTPU_HM_TEST_STEPS"] = "20"
    env["MXTPU_HM_TEST_SLEEP_MS"] = "80"
    env["MXTPU_HM_NAN_STEP"] = "7"
    r = subprocess.run([sys.executable, _DRIVER], env=env,
                       capture_output=True, text=True,
                       timeout=_TIMEOUT_S + 60)
    assert r.returncode == 0, \
        f"health_cluster failed\nstdout:{r.stdout}\nstderr:{r.stderr[-3000:]}"
    verdict_lines = [ln for ln in r.stdout.splitlines()
                     if ln.startswith("HEALTH_SMOKE_OK ")]
    assert verdict_lines, f"no verdict line in {r.stdout!r}"
    verdict = json.loads(verdict_lines[0][len("HEALTH_SMOKE_OK "):])
    # the driver already asserted the detailed contract; re-assert the
    # headline numbers here so a weakened driver can't silently pass
    assert verdict["slowest_rank"] == 1
    assert verdict["skew_ms"] >= 0.4 * 80
    assert verdict["nan_alerts_rank0"] >= 1
    assert os.path.exists(verdict["merged_file"])
