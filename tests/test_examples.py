"""Examples stay runnable (slow tier): each script is executed with tiny
arguments in a subprocess on the CPU backend."""
import os
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, *args):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    out = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "examples", script), *args],
        capture_output=True, text=True, timeout=500, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout


@pytest.mark.slow
def test_example_mnist():
    out = _run("train_mnist_gluon.py", "--epochs", "1",
               "--num-examples", "512", "--batch-size", "64")
    assert "accuracy=" in out


@pytest.mark.slow
def test_example_resnet_mesh():
    out = _run("train_resnet_mesh.py", "--model", "resnet18_v1", "--dp", "8",
               "--batch-size", "16", "--size", "32", "--steps", "2",
               "--dtype", "float32")
    assert "img/s" in out


@pytest.mark.slow
def test_example_bert():
    out = _run("bert_pretrain_toy.py", "--steps", "4", "--layers", "1",
               "--seq-len", "32")
    assert "loss" in out


@pytest.mark.slow
def test_example_bert_ring():
    out = _run("bert_pretrain_toy.py", "--steps", "2", "--layers", "1",
               "--seq-len", "64", "--ring-sp", "8")
    assert "loss" in out


@pytest.mark.slow
def test_example_ssd():
    out = _run("train_ssd_toy.py", "--epochs", "1")
    assert "detect()" in out


@pytest.mark.slow
def test_example_rnn_bucketing():
    out = _run("train_rnn_bucketing.py", "--num-sentences", "800",
               "--epochs", "3")
    assert "perplexity=" in out


@pytest.mark.slow
def test_example_quantize_inference():
    out = _run("quantize_inference.py")
    assert "agreement" in out


@pytest.mark.slow
def test_example_onnx():
    out = _run("onnx_export_import.py", "--steps", "5")
    assert "OK: ONNX round trip preserves predictions" in out


@pytest.mark.slow
def test_example_train_lm():
    out = _run("train_lm.py", "--steps", "60")
    assert "greedy :" in out and "loss" in out


@pytest.mark.slow
def test_example_train_lm_distributed(tmp_path):
    out = _run("train_lm_distributed.py", "--steps", "12",
               "--save-every", "6", "--ckpt-dir", str(tmp_path / "ck"))
    assert "dp mesh" in out and "checkpoint ->" in out
    out2 = _run("train_lm_distributed.py", "--steps", "16",
                "--save-every", "8", "--ckpt-dir", str(tmp_path / "ck"))
    assert "resumed from step" in out2


@pytest.mark.slow
def test_example_estimator_mnist(tmp_path):
    out = _run("estimator_mnist.py", "--epochs", "2",
               "--num-examples", "512", "--ckpt-dir", str(tmp_path))
    acc = float(out.split("final validation accuracy=")[1].split()[0])
    assert acc > 0.5, acc  # the blobs are deliberately learnable
    assert (tmp_path / "lenet-best.params").exists()
