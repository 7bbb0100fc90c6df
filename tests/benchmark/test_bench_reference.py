"""The plain reference against the program on a tiny `transformer_lm`, the
near-tie rule, and the operation count."""

import numpy as np
import pytest

from benchmark.harness import cells, flops

ref = cells.load_module("reference", "pre_ln_lm")


@pytest.fixture(scope="module")
def tiny_lm():
    from deeplearning4j_tpu.models import zoo
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    return ComputationGraph(zoo.transformer_lm(
        vocab_size=96, t=24, d_model=32, n_heads=4, n_blocks=2,
        decode_cache_length=32, seed=5)).init()


def test_reference_agrees_with_net_output(tiny_lm):
    """float32 on the CPU, no kernels on either side: the two differ only
    in the order of sums, so 1e-5 of the peak probability is generous and
    far tighter than any bf16 path (which differs by 1e-2) could meet."""
    import jax.numpy as jnp

    ids = np.random.RandomState(0).randint(1, 96, 20)
    want = np.asarray(ref.forward(tiny_lm.params_tree, jnp.asarray(ids),
                                  n_heads=4, n_blocks=2))
    got = np.asarray(tiny_lm.output(ids.astype(np.float32)[None, :, None])[0])[0]
    assert want.shape == got.shape == (20, 96)
    assert np.allclose(want.sum(-1), 1.0, atol=1e-5)
    assert np.max(np.abs(got - want)) <= 1e-5 * np.max(want)
    assert (got.argmax(-1) == want.argmax(-1)).all()


def test_reference_is_causal(tiny_lm):
    import jax.numpy as jnp

    ids = np.random.RandomState(1).randint(1, 96, 16)
    full = np.asarray(ref.forward(tiny_lm.params_tree, jnp.asarray(ids), 4, 2))
    padded = np.concatenate([ids[:9], np.zeros(7, ids.dtype)])
    head = np.asarray(ref.forward(tiny_lm.params_tree, jnp.asarray(padded),
                                  4, 2))
    assert np.allclose(full[:9], head[:9], atol=1e-7)


def table(rows):
    """`probs_of` over a fixed table: the distribution after n tokens."""
    return lambda ids: np.asarray(rows[len(ids)], np.float64)


ROWS = {2: [0.1, 0.6, 0.3], 3: [0.5, 0.1, 0.4], 4: [0.30, 0.295, 0.405]}


@pytest.mark.parametrize("served,verdict,compared", [
    ([7, 8, 1, 0, 2], "equal", 3),
    ([7, 8, 1, 2, 2], "differs", 2),        # 0.4 / 0.5 is no near-tie
    ([7, 8, 1, 0, 0], "differs", 3),        # 0.30 / 0.405
    ([7, 9, 1, 0, 2], "differs", 0),        # the prompt came back changed
    ([7, 8, 1, 0], "differs", 0),           # too few tokens
])
def test_greedy_agreement(served, verdict, compared):
    got, _, n = ref.greedy_agreement(table(ROWS), [7, 8], served, 3, 0.98)
    assert got.startswith(verdict) and n == compared


def test_near_tie_forks_and_stops_comparing():
    rows = dict(ROWS)
    rows[3] = [0.5, 0.1, 0.495]
    verdict, worst, n = ref.greedy_agreement(
        table(rows), [7, 8], [7, 8, 1, 2, 1], 3, 0.98)
    assert verdict == "near_tie" and worst == pytest.approx(0.99) and n == 2


def test_a_token_the_program_cannot_condition_on_ends_the_comparison():
    verdict, _, n = ref.greedy_agreement(
        table(ROWS), [7, 8], [7, 8, 1, 2, 2], 3, 0.98,
        comparable=lambda token: token != 1)
    assert verdict == "equal" and n == 1


def test_matmul_macs_counts_dots_convs_and_scans():
    import jax
    import jax.numpy as jnp

    def fn(x, w, img, k):
        y = x @ w                                            # 4*8*16
        z = jax.lax.conv_general_dilated(
            img, k, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))      # 2*6*6*5 * 3*3*4
        def body(c, _):
            return c @ w.T @ w, None                         # 2 * 4*8*16 each
        c, _ = jax.lax.scan(body, y, None, length=3)
        return c.sum() + z.sum()

    n = flops.forward_macs(
        fn, jnp.ones((4, 8)), jnp.ones((8, 16)), jnp.ones((2, 6, 6, 4)),
        jnp.ones((3, 3, 4, 5)))
    assert n == 4 * 8 * 16 + 2 * 6 * 6 * 5 * 3 * 3 * 4 + 3 * 2 * 4 * 8 * 16


def test_resnet50_forward_is_about_4_gmacs():
    """He et al. give 3.8e9 multiply-adds for the 50-layer net; with the
    stride in the 3x3 convolution (as `models/resnet.py` has it) and the
    1000-way classifier it is 4.09e9."""
    import jax

    from deeplearning4j_tpu.models.resnet import resnet50
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    net = ComputationGraph(resnet50(n_classes=1000, image=224,
                                    dtype="float32"))
    params = jax.eval_shape(lambda: ComputationGraph(resnet50(
        n_classes=1000, image=224, dtype="float32")).init().params_tree)
    state = jax.eval_shape(lambda: ComputationGraph(resnet50(
        n_classes=1000, image=224, dtype="float32")).init().state)

    def forward(p, s, x):
        return net._forward_fn(p, s, [x], None, False, None)[0]

    macs = flops.forward_macs(
        forward, params, state,
        jax.ShapeDtypeStruct((1, 224, 224, 3), np.float32))
    assert 3.8e9 <= macs <= 4.2e9
