"""jit.save/load (StableHLO export) + inference Predictor tests.

Reference test model: dygraph-to-static save/load parity tests
(``python/paddle/fluid/tests/unittests/dygraph_to_static/``, SURVEY.md §4).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
import paddle_tpu.nn as nn
from paddle_tpu.inference import Config, create_predictor
from paddle_tpu.jit import InputSpec, TranslatedLayer


class SmallNet(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc1 = nn.Linear(8, 16)
        self.fc2 = nn.Linear(16, 4)
        self.bn = nn.BatchNorm1D(16)
        self.drop = nn.Dropout(0.5)

    def forward(self, x):
        h = nn.functional.relu(self.bn(self.fc1(x)))
        return self.fc2(self.drop(h))


def test_save_load_value_parity(tmp_path):
    net = SmallNet()
    net.eval()
    x = jnp.asarray(np.random.default_rng(0).normal(size=(4, 8)), jnp.float32)
    want = np.asarray(net(x))
    path = str(tmp_path / "model" / "net")
    pt.jit.save(net, path, input_spec=[InputSpec((4, 8), "float32")])
    loaded = pt.jit.load(path)
    assert isinstance(loaded, TranslatedLayer)
    got = np.asarray(loaded(x))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_save_captures_eval_mode(tmp_path):
    """Dropout must be inert in the exported program even if the layer was
    in train mode when saved (save() flips to eval, like the reference)."""
    net = SmallNet()
    net.train()
    path = str(tmp_path / "net")
    pt.jit.save(net, path, input_spec=[InputSpec((2, 8), "float32")])
    assert net.training  # restored
    loaded = pt.jit.load(path)
    x = jnp.ones((2, 8), jnp.float32)
    net.eval()
    want = np.asarray(net(x))  # eval-mode reference
    np.testing.assert_allclose(np.asarray(loaded(x)), want, rtol=1e-5,
                               atol=1e-6)


def test_multi_dynamic_inputs_share_scope(tmp_path):
    """Two inputs with dynamic batch dims must export together (single
    symbolic scope)."""

    class TwoDyn(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc = nn.Linear(8, 4)

        def forward(self, a, b):
            return self.fc(a) + self.fc(b)

    net = TwoDyn()
    net.eval()
    path = str(tmp_path / "net")
    pt.jit.save(net, path, input_spec=[InputSpec((None, 8), "float32"),
                                       InputSpec((None, 8), "float32")])
    loaded = pt.jit.load(path)
    out = loaded(jnp.ones((5, 8), jnp.float32), jnp.ones((5, 8), jnp.float32))
    assert out.shape == (5, 4)


def test_predictor_unset_input_clear_error(tmp_path):
    net = SmallNet()
    net.eval()
    path = str(tmp_path / "net")
    pt.jit.save(net, path, input_spec=[InputSpec((2, 8), "float32")])
    predictor = create_predictor(Config(path))
    # output handles are addressable before the first run
    assert predictor.get_output_names() == ["out0"]
    assert predictor.get_output_handle("out0").shape is None
    with pytest.raises(RuntimeError, match="inputs not set"):
        predictor.run()


def test_predictor_cpu_device_selection(tmp_path):
    net = SmallNet()
    net.eval()
    path = str(tmp_path / "net")
    pt.jit.save(net, path, input_spec=[InputSpec((2, 8), "float32")])
    config = Config(path)
    config.disable_gpu()
    predictor = create_predictor(config)
    x = np.ones((2, 8), np.float32)
    want = np.asarray(net(jnp.asarray(x)))
    np.testing.assert_allclose(predictor.run([x])[0], want, rtol=1e-5,
                               atol=1e-6)


def test_dynamic_batch_export(tmp_path):
    net = SmallNet()
    net.eval()
    path = str(tmp_path / "net")
    pt.jit.save(net, path, input_spec=[InputSpec((None, 8), "float32")])
    loaded = pt.jit.load(path)
    for bs in (1, 3, 17):
        out = loaded(jnp.ones((bs, 8), jnp.float32))
        assert out.shape == (bs, 4)


def test_translated_layer_state_dict_roundtrip(tmp_path):
    net = SmallNet()
    net.eval()
    path = str(tmp_path / "net")
    pt.jit.save(net, path, input_spec=[InputSpec((2, 8), "float32")])
    loaded = pt.jit.load(path)
    sd = loaded.state_dict()
    assert len(sd) > 0
    # zero every param -> output changes; restore -> parity again
    x = jnp.ones((2, 8), jnp.float32)
    want = np.asarray(loaded(x))
    zeroed = {k: jnp.zeros_like(v) for k, v in sd.items()}
    loaded.set_state_dict(zeroed)
    assert not np.allclose(np.asarray(loaded(x)), want)
    loaded.set_state_dict(sd)
    np.testing.assert_allclose(np.asarray(loaded(x)), want, rtol=1e-6)


def test_predictor_handle_api(tmp_path):
    net = SmallNet()
    net.eval()
    x = np.random.default_rng(1).normal(size=(4, 8)).astype(np.float32)
    want = np.asarray(net(jnp.asarray(x)))
    path = str(tmp_path / "net")
    pt.jit.save(net, path, input_spec=[InputSpec((4, 8), "float32")])

    config = Config(path + ".pdmodel")
    predictor = create_predictor(config)
    names = predictor.get_input_names()
    assert len(names) == 1
    predictor.get_input_handle(names[0]).copy_from_cpu(x)
    outs = predictor.run()
    np.testing.assert_allclose(outs[0], want, rtol=1e-5, atol=1e-6)
    h = predictor.get_output_handle(predictor.get_output_names()[0])
    np.testing.assert_array_equal(h.copy_to_cpu(), outs[0])


def test_save_requires_input_spec(tmp_path):
    with pytest.raises(ValueError):
        pt.jit.save(SmallNet(), str(tmp_path / "x"))


def test_save_multi_input_and_example_arrays(tmp_path):
    class TwoIn(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc = nn.Linear(4, 2)

        def forward(self, a, b):
            return self.fc(a) + self.fc(b)

    net = TwoIn()
    net.eval()
    a = jnp.ones((3, 4), jnp.float32)
    b = jnp.full((3, 4), 2.0, jnp.float32)
    want = np.asarray(net(a, b))
    path = str(tmp_path / "two")
    pt.jit.save(net, path, input_spec=[a, b])  # concrete example arrays
    out = np.asarray(pt.jit.load(path)(a, b))
    np.testing.assert_allclose(out, want, rtol=1e-6)


# --------------------------------------- non-Python serving consumer (r3)
def test_c_api_consumer_matches_python_predictor(tmp_path):
    """The plain-C demo (tools/infer_demo.c, dlopen'ing the C inference
    API) reproduces the Python Predictor's outputs on a jit.save artifact —
    the capi_exp-style non-Python serving path, demonstrated end to end."""
    import os
    import subprocess
    import sys

    from paddle_tpu.inference import build_capi, build_demo
    from paddle_tpu.jit import save as jit_save

    pt.seed(4)
    net = SmallNet()
    net.eval()
    prefix = str(tmp_path / "cmodel")
    jit_save(net, prefix, input_spec=[InputSpec([2, 8], "float32")])

    rng = np.random.default_rng(0)
    x = rng.standard_normal((2, 8)).astype(np.float32)
    ref = create_predictor(Config(prefix)).run([x])[0]

    lib = build_capi()
    demo = build_demo()
    inp = tmp_path / "input.bin"
    inp.write_bytes(x.tobytes())

    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.getcwd()] + [p for p in sys.path if "site-packages" in p])
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [demo, lib, prefix, str(inp), "2", "8"],
        capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode == 0, proc.stderr
    lines = proc.stdout.strip().splitlines()
    assert lines[0].split() == ["shape", "2", "4"]
    got = np.asarray([float(v) for v in lines[1:]], np.float32).reshape(2, 4)
    np.testing.assert_allclose(got, np.asarray(ref), rtol=1e-5, atol=1e-6)
