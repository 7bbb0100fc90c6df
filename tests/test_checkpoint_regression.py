"""Checkpoint-format backward compatibility against COMMITTED golden
fixtures (reference analog: `regressiontest/RegressionTest050.java` et al. —
the reference commits serialized models from old versions and asserts they
still load and predict).

The fixtures in `tests/fixtures/` were written once (see the generation
recipe in the expect JSON's sibling commit) and must keep loading forever:
the zip format is load-bearing for failure recovery (`util/failure.py`
rolls back to the newest healthy checkpoint), so silent format drift would
break rollback of existing checkpoints in the field.
"""

import json
import os

import numpy as np

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.util.checkpoint import load_checkpoint
from deeplearning4j_tpu.util.model_serializer import load_model

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")


def _golden_data():
    r = np.random.RandomState(77)
    X = r.randn(12, 5).astype("float32")
    Y = np.eye(3)[r.randint(0, 3, 12)].astype("float32")
    return X, Y


def _expect():
    with open(os.path.join(FIXTURES, "golden_expect_v1.json")) as f:
        return json.load(f)


def test_golden_model_zip_loads_and_predicts():
    exp = _expect()
    net = load_model(os.path.join(FIXTURES, "golden_model_v1.zip"))
    assert isinstance(net, MultiLayerNetwork)
    assert net.iteration == exp["iteration"]
    assert net.params().size == exp["params_sha_len"]
    np.testing.assert_allclose(net.params()[:16], exp["params_first16"],
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(net.updater_state_flat()[:16],
                               exp["updater_first16"], rtol=1e-6, atol=1e-7)
    X, _ = _golden_data()
    np.testing.assert_allclose(net.output(X), np.asarray(exp["output"]),
                               rtol=1e-5, atol=1e-6)


def test_golden_checkpoint_resumes_identically():
    """Load the committed checkpoint (params + updater + RNG continuation)
    and take one training step: the score must match the recorded value —
    the exact contract `util/failure.py` rollback depends on.

    Tolerance policy: the expect value is regenerated whenever an
    intentional numeric change lands in the traced train step, by running
    THIS test's exact recipe under the conftest environment (x64, 8 virtual
    CPU devices, hermetic `JAX_COMPILATION_CACHE_DIR`) and copying
    `net.score_value` into `score_after_resume_step`. The value must first
    prove device-count independent (identical under 1 and 8 devices) and
    eager/jit consistent to <1e-6; the assertion bound is then 1e-4 — f32
    params through one f32 step leave ~1e-7 jit-fusion slack, so 1e-4
    flags real semantic drift while ignoring instruction-ordering noise.
    The step draws a dropout mask (layer 0, p=0.8), so the value also moves
    with jax's default PRNG: 0.78384 under the non-partitionable threefry
    of the jax the fixture was written with, 0.83084 under jax 0.9.0's
    partitionable default (same checkpoint, same data; checked at 1 and 8
    devices and with jit disabled).
    Never regenerate against a warm compile cache: a stale AOT
    entry replays an executable serialized from OLDER library code (the
    fingerprint hashes config/shapes/jax versions, not library code),
    which is how the previous expect value went bad."""
    exp = _expect()
    X, Y = _golden_data()
    net = load_checkpoint(os.path.join(FIXTURES, "golden_checkpoint_v1.zip"))
    net.fit(DataSet(X, Y))
    assert abs(float(net.score_value) - exp["score_after_resume_step"]) < 1e-4
