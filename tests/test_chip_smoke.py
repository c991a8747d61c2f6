"""chip_smoke.py is the proof that the main paths start on the chip — so
the one thing tier-1 must hold it to is that a CPU run can never pass for
a chip run, while its whole control flow still gets rehearsed here."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")
PHASES = ["device", "eager", "kernels", "train-resnet50", "train-lm",
          "serve"]


def _run(args, tmp_path, **env_over):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_over)
    env.pop("XLA_FLAGS", None)          # one CPU device, as on one chip
    return subprocess.run([sys.executable, SMOKE, *args], env=env,
                          cwd=str(tmp_path), capture_output=True, text=True,
                          timeout=900)


def test_tiny_rehearses_every_phase_and_never_passes_on_cpu(tmp_path):
    cache = tmp_path / "cc"
    r = _run(["--tiny"], tmp_path, JAX_COMPILATION_CACHE_DIR=str(cache))
    lines = [json.loads(l) for l in r.stdout.splitlines()
             if l.startswith("{")]
    assert [l["phase"] for l in lines[:-1]] == PHASES, r.stderr[-3000:]
    assert all(l["ok"] for l in lines[:-1])
    last = lines[-1]
    assert r.stdout.rstrip().splitlines()[-1] == json.dumps(last)
    assert last["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    assert last["ok"] is not True and r.returncode != 0
    # the rehearsal walked the real control flow: no compile inside a timed
    # window, kernels selected (interpreted here), requests coalesced
    by = {l["phase"]: l for l in lines[:-1]}
    assert by["device"]["native_runtime"] and by["device"]["cache_canary_ok"]
    assert by["train-resnet50"]["compiles_in_window"] == 0
    assert by["train-lm"]["compiles_in_window"] == 0
    assert by["train-lm"]["pallas"]["pallas.selected.flash_attention"] > 0
    assert len(by["kernels"]["kernels"]) >= 6
    assert max(by["serve"]["batch_sizes"]) > 1
    # JAX_COMPILATION_CACHE_DIR is the only cache directory the run used
    assert by["device"]["compile_cache_dir"] == str(cache)
    assert any(cache.iterdir())
    assert not (tmp_path / ".jax_cache").exists()


def test_full_size_refuses_to_start_without_a_tpu(tmp_path):
    r = _run([], tmp_path)
    assert r.returncode != 0
    assert r.stdout == ""               # no chip, no result line at all
    assert "needs a TPU" in r.stderr


@pytest.mark.parametrize("given", ["/some/dir", None])
def test_compile_cache_directory_rule(tmp_path, given):
    """JAX_COMPILATION_CACHE_DIR, when set, is left alone; unset, the cache
    is the fixed <checkout>/.jax_cache."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if given:
        env["JAX_COMPILATION_CACHE_DIR"] = given
    code = ("from incubator_mxnet_tpu.runtime.cache_guard import "
            "use_compile_cache as u; import jax; "
            "print(u('/a/checkout')); "
            "print(jax.config.jax_compilation_cache_dir)")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       cwd=str(tmp_path), capture_output=True, text=True,
                       timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    want = given or "/a/checkout/.jax_cache"
    assert r.stdout.split() == [want, want]
