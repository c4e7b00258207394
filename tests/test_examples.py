"""Example-script smoke tier (SURVEY §4 'Tutorials/docs tests' analog:
the reference CI executes its tutorials; here every example/ script runs
end-to-end at a tiny config in a subprocess so the documented entry
points cannot rot)."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, *args, timeout=240):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # scripts that need a mesh self-provision
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "example", script), *args],
        env=env, capture_output=True, text=True, timeout=timeout,
    )
    assert proc.returncode == 0, \
        f"{script} failed:\n{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}"
    return proc.stdout


def test_train_mnist_gluon():
    out = _run("train_mnist.py", "--benchmark", "--epochs", "1",
               "--batch-size", "64")
    assert "epoch" in out.lower() or "accuracy" in out.lower()


def test_train_mnist_module():
    _run("train_mnist.py", "--benchmark", "--module", "--epochs", "1",
         "--batch-size", "64")


def test_sparse_linear_classification():
    out = _run("sparse_linear_classification.py", "--epochs", "1",
               "--num-features", "2000", "--batch-size", "256")
    assert "final-accuracy" in out


def test_quantize_int8():
    out = _run("quantize_int8.py", "--epochs", "1")
    assert "agreement" in out


def test_dcgan():
    out = _run("dcgan.py", "--epochs", "1", "--steps-per-epoch", "4",
               "--batch-size", "16")
    assert "sample-std" in out


def test_model_parallel_lstm():
    out = _run("model_parallel_lstm.py", timeout=300)
    assert "model-parallel == replicated: OK" in out


def test_word_language_model():
    out = _run("word_language_model.py", "--epochs", "1",
               "--batch-size", "8", "--bptt", "4")
    assert out.strip()


def test_ctc_ocr():
    out = _run("ctc_ocr.py", "--smoke")
    assert "smoke ok" in out


def test_lstm_bucketing():
    """The sym.RNN mega-op + BucketingModule path ([U:example/rnn/
    bucketing/] analog): perplexity must fall and buckets share weights."""
    out = _run("lstm_bucketing.py", "--epochs", "2", timeout=420)
    assert "final-perplexity" in out


def test_onnx_roundtrip_example():
    out = _run("onnx_roundtrip.py", "--epochs", "1", "--n", "256")
    assert "ONNX_ROUNDTRIP_OK" in out


def test_lstm_bucketing_cell_api():
    out = _run("lstm_bucketing.py", "--epochs", "2", "--cell-api")
    assert "final-perplexity" in out
