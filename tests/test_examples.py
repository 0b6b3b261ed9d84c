"""Examples must actually run (reference keeps examples working;
smoke-run each with small settings)."""

import os
import subprocess
import sys

import pytest

EXAMPLES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "examples")
ENV = dict(os.environ, JAX_PLATFORMS="cpu",
           XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                      + " --xla_force_host_platform_device_count=4"))


def _run(script, *args, timeout=900):
    r = subprocess.run(
        [sys.executable, os.path.join(EXAMPLES, script), *args],
        capture_output=True, text=True, env=ENV, timeout=timeout,
        cwd=os.path.dirname(EXAMPLES))
    assert r.returncode == 0, f"{script} failed:\n{r.stderr[-2000:]}"
    return r.stdout


class TestExamples:
    def test_lenet_mnist(self):
        out = _run("lenet_mnist.py", "--epochs", "2", "--batch", "128")
        assert "Accuracy" in out
        assert "checkpoint round trip OK" in out

    def test_data_parallel_resnet(self):
        out = _run("data_parallel_resnet.py", "--img", "32",
                   "--steps", "3")
        assert "4 devices" in out
        assert "final loss" in out

    def test_word2vec(self):
        out = _run("word2vec_text.py")
        assert "nearest(king):" in out
        assert "vectors written" in out

    def test_elastic_transformer(self):
        out = _run("elastic_transformer.py", "--epochs", "4")
        assert "restart == uninterrupted: OK" in out
        assert "Accuracy after resume" in out

    def test_keras_import_finetune(self):
        pytest.importorskip("keras")
        out = _run("keras_import_finetune.py")
        assert "max |keras - ours|" in out
        assert "fine-tuned accuracy" in out

    def test_streaming_generation(self):
        out = _run("streaming_generation.py", "--epochs", "1",
                   "--gen-tokens", "8")
        assert "bounded session matches eager decode OK" in out

    def test_long_context_lm(self):
        out = _run("long_context_lm.py", "--epochs", "8")
        assert "data=2 x seq=2" in out
        assert "matches single-device params: True" in out

    def test_tpu_transformer_generate_names_its_platform(self, tmp_path):
        # ENV pins JAX_PLATFORMS=cpu: the example runs on the backend
        # jax gives it, says which, and runs end to end with profiler
        # + compile watch + trace export
        trace_path = str(tmp_path / "t.json")
        out = _run("tpu_transformer_generate.py", "--epochs", "1",
                   "--gen-tokens", "8", "--trace", trace_path)
        assert "running on platform cpu" in out
        assert "generated:" in out
        assert "step profile:" in out
        assert "compile watch:" in out
        import json as _json
        with open(trace_path) as f:
            doc = _json.load(f)
        names = {e["name"] for e in doc["traceEvents"]}
        assert {"train", "generate", "train_step"} <= names
