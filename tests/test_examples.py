"""Examples must stay runnable (reference CI runs example/ scripts)."""
import os
import runpy
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(name, argv=("x",)):
    old = sys.argv
    sys.argv = list(argv)
    try:
        runpy.run_path(os.path.join(REPO, "examples", name),
                       run_name="__main__")
    finally:
        sys.argv = old


def test_example_quantize():
    _run("quantize_inference.py")


@pytest.mark.slow
def test_example_ring_attention():
    # subprocess: the 8-virtual-device mesh needs XLA_FLAGS set before jax
    # initializes, which is impossible in this already-initialized process
    env = dict(os.environ)
    env["XLA_FLAGS"] = env.get("XLA_FLAGS", "") + \
        " --xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-c",
         "import jax; jax.config.update('jax_platforms', 'cpu');"
         "import runpy, sys; sys.argv=['x'];"
         f"runpy.run_path(r'{os.path.join(REPO, 'examples', 'long_context_ring_attention.py')}',"
         "run_name='__main__')"],
        env=env, capture_output=True, timeout=300, cwd=REPO)
    assert r.returncode == 0, r.stderr.decode()[-2000:]
    assert b"ring attention over 8 devices" in r.stdout, r.stdout


@pytest.mark.slow
def test_example_mnist_one_epoch():
    # a full synthetic epoch (~10s subprocess) — slow tier; the quick
    # gate keeps the shorter example scripts below
    _run("train_mnist_gluon.py", ("x", "--epochs", "1"))


def test_example_sparse_embedding():
    _run("sparse_embedding_lm.py", ("x", "--vocab", "2000", "--steps", "8"))


def test_example_onnx_roundtrip(tmp_path):
    _run("onnx_export_import.py", ("x", "--out",
                                   str(tmp_path / "m.onnx")))


def test_example_moe_pipeline():
    # in-process: conftest already provisioned the 8-device CPU mesh
    _run("moe_pipeline_parallel.py")


@pytest.mark.slow
def test_example_lstm_lm():
    _run("train_lstm_lm.py", ("x", "--steps", "60"))


@pytest.mark.slow
def test_example_ssd():
    _run("ssd_detection.py", ("x", "--steps", "25", "--batch", "8"))


@pytest.mark.slow
def test_example_bert():
    _run("train_bert_classifier.py")


def test_example_pipeline_trainer():
    _run("pipeline_trainer.py", ("x", "--steps", "12", "--width", "16"))


@pytest.mark.slow
def test_example_convlstm():
    _run("convlstm_video.py", ("x", "--steps", "200"))


def test_example_wikitext_lm_pretrained_embedding():
    _run("wikitext_lm_pretrained_embedding.py", argv=("x", "--steps", "25"))
