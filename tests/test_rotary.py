"""The registry's `rotary` (`kernels/rotary.py`): the Pallas body, run in
interpret mode, against `dsa.rope_xla` (its parity reference and XLA
candidate) forward, backward and under `vmap`; what `auto` resolves where;
the dispatch counter. The chip's compiler sees the body in
`tests/test_chip_compile.py`."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import observability as obs
from deeplearning4j_tpu.kernels import registry, rotary
from deeplearning4j_tpu.nn.layers import dsa

YARN = {"rope_type": "yarn", "factor": 16,
        "original_max_position_embeddings": 32, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782}


@pytest.fixture(autouse=True)
def _registry_env(monkeypatch):
    for var in ("DL4J_TPU_KERNELS", "DL4J_TPU_KERNEL_ROTARY"):
        monkeypatch.delenv(var, raising=False)
    registry.clear_cache()
    yield
    registry.clear_cache()


def _forced(monkeypatch):
    monkeypatch.setenv("DL4J_TPU_KERNEL_ROTARY", "pallas")
    registry.clear_cache()


def _ulps(got, want):
    """Largest difference between two arrays of one float dtype in units in
    the last place of the largest magnitude `want` holds: a sum that cancels
    near zero has tiny ulps of its own, and one rounding more or less there
    is no error of the rotation's."""
    got, want = np.asarray(got), np.asarray(want)
    assert got.dtype == want.dtype and got.shape == want.shape
    top = np.max(np.abs(want.astype(np.float32)))
    ulp = 2.0 ** (np.floor(np.log2(top)) - jnp.finfo(want.dtype).nmant)
    diff = np.abs(got.astype(np.float32) - want.astype(np.float32))
    return float(np.max(diff) / ulp)


def _x(shape, dtype, key=0):
    return jax.random.normal(jax.random.PRNGKey(key), shape,
                             jnp.float32).astype(dtype)


# (S, H, D): mellum's and keye's heads of 128, the 64 of kimi's rotary slice
# and keye's indexer (two heads to a 128-lane row in a lanes-minor view), a
# head of 256, one head of 64 (a latent layer's shared rotary key), 32.
SHAPES = {"D128": (64, 4, 128), "D64": (128, 4, 64), "D256": (64, 2, 256),
          "one_head_D64": (1024, 1, 64), "D32": (64, 8, 32)}


@pytest.mark.parametrize("scaling", [None, YARN], ids=["plain", "yarn"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_the_kernel_rotates_as_the_xla_expression_does(monkeypatch, shape,
                                                       dtype, scaling):
    """Forward and the gradient's `custom_vjp` (the same body by the opposite
    angle) against `rope_xla` and its autodiff: the same float32 arithmetic
    and rounding, so at most one ulp apart (XLA's CPU backend contracts some
    products into fused multiply-adds the interpreter does not); in bf16
    the roundings meet and most elements agree to the bit."""
    _forced(monkeypatch)
    S, H, D = SHAPES[shape]
    x = _x((S, D) if H == 1 else (S, H, D), dtype)
    g = _x(x.shape, dtype, key=1)
    got, vjp = jax.vjp(lambda x: dsa.rope(x, 1e4, scaling), x)
    want, vjp_ref = jax.vjp(lambda x: dsa.rope_xla(x, 1e4, scaling), x)
    assert got.dtype == want.dtype and got.shape == x.shape
    assert _ulps(got, want) <= 1
    assert _ulps(vjp(g)[0], vjp_ref(g)[0]) <= 1
    assert any(r.kernel == "rotary" and r.impl == "pallas"
               for r in registry.resolved())


def test_the_tables_are_the_xla_expressions_cosines_and_sines():
    """`tables` takes cos and sin of the products `rope_xla` takes them of
    and applies YaRN's factor as it does: equal to the bit."""
    S, D = 256, 128
    t = jnp.arange(S, dtype=jnp.float32)
    inv, mscale = dsa.rope_frequencies(D, 5e5, YARN, jnp.float32)
    c, s = rotary.tables(t, inv, mscale)
    assert c.shape == s.shape == (D // 2, S) and c.dtype == jnp.float32
    ang = t[:, None] * inv[None, :]
    np.testing.assert_array_equal(np.asarray(c),
                                  np.asarray(jnp.cos(ang) * mscale).T)
    np.testing.assert_array_equal(np.asarray(s),
                                  np.asarray(jnp.sin(ang) * mscale).T)


def test_the_backward_pass_is_the_rotation_by_the_opposite_angle(
        monkeypatch):
    """The rotation is orthogonal: its gradient undoes it."""
    _forced(monkeypatch)
    x = _x((64, 4, 128), "float32")
    y, vjp = jax.vjp(lambda x: dsa.rope(x, 1e4, YARN), x)
    m = YARN["attention_factor"]
    np.testing.assert_allclose(np.asarray(vjp(y)[0]), np.asarray(x) * m * m,
                               rtol=1e-5, atol=1e-5)


def test_the_kernel_batches_under_vmap(monkeypatch):
    """A batch of more than one sequence runs `_one_sequence` under `vmap`:
    the `pallas_call` gains a grid axis, forward and backward."""
    _forced(monkeypatch)
    xb = _x((3, 128, 4, 64), "bfloat16")
    gb = _x(xb.shape, "bfloat16", key=2)
    got, vjp = jax.vjp(jax.vmap(lambda x: dsa.rope(x, 1e4)), xb)
    want, vjp_ref = jax.vjp(jax.vmap(lambda x: dsa.rope_xla(x, 1e4)), xb)
    assert _ulps(got, want) <= 1
    assert _ulps(vjp(gb)[0], vjp_ref(gb)[0]) <= 1


def test_a_layer_under_vmap_trains_through_the_kernel(monkeypatch):
    """`SelfAttentionLayer` at batch 2 (the `vmap` path of
    `extended_attention_apply`): loss and gradients with the kernel agree
    with the XLA expression's."""
    from deeplearning4j_tpu.nn.conf.layers import SelfAttentionLayer

    conf = SelfAttentionLayer(
        n_in=64, n_out=64, n_heads=4, n_kv_heads=2, head_dim=32,
        rope_theta=1e4, qk_norm_eps=1e-6, causal=True, rope_scaling=YARN)
    keys = jax.random.split(jax.random.PRNGKey(3), 8)
    params = {n: 0.1 * jax.random.normal(k, shape, jnp.float32)
              for k, (n, shape) in zip(keys, conf.param_shapes().items())}
    x = _x((2, 64, 64), "float32", key=4)

    def loss(p):
        out, _, _ = dsa.extended_attention_apply(conf, p, {}, x)
        return jnp.sum(out * out)

    want = jax.value_and_grad(loss)(params)
    _forced(monkeypatch)
    got = jax.value_and_grad(loss)(params)
    np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=1e-5)
    for n in params:
        w = np.asarray(want[1][n])
        np.testing.assert_allclose(np.asarray(got[1][n]), w, rtol=1e-4,
                                   atol=1e-5 * np.max(np.abs(w)))


def test_a_train_step_through_the_kernel_matches_the_xla_expressions(
        monkeypatch):
    """`mellum2_12b_a2_5b`'s rehearsal network (window and full layers, YaRN
    on the full one) with heads of 32, two train steps through the engine's
    compiled step in float32: with the kernel forced the parameters land
    where the XLA expression's do."""
    from benchmark.harness import cells
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    cell = cells.Cell("mellum2_12b_a2_5b.fit_seq16k", rehearsal=True)
    config = cells.load_module("configs", "mellum2_12b_a2_5b")
    sizes = dict(cell.sizes, dtype_policy={"name": "float32"})
    S, V = int(sizes["seq_len"]), int(sizes["held"]["ids"])
    ids = np.random.default_rng(5).integers(0, V, (1, S + 1)).astype(np.int32)
    batch = DataSet(ids[:, :-1], ids[:, 1:], None,
                    np.full((1, S), 1.0 / S, np.float32))

    def trained():
        net = ComputationGraph(config.make_conf(sizes, 3, head_dim=32)).init()
        for _ in range(2):
            net.fit(batch)
        return jax.tree_util.tree_map(np.asarray, net.params_tree)

    want = trained()
    _forced(monkeypatch)
    got = trained()
    assert {r.impl for r in registry.resolved() if r.kernel == "rotary"} \
        == {"pallas"}
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-4,
                                   atol=1e-5 * np.max(np.abs(w)))


def test_auto_keeps_the_xla_expression_off_the_tpu():
    """The CPU's `auto` is the pre-registry program: `rope_xla`, to the bit."""
    x = _x((64, 4, 128), "bfloat16")
    np.testing.assert_array_equal(np.asarray(dsa.rope(x, 1e4, YARN)),
                                  np.asarray(dsa.rope_xla(x, 1e4, YARN)))
    res = [r for r in registry.resolved() if r.kernel == "rotary"]
    assert [r.impl for r in res] == ["xla"]
    assert "off-TPU" in res[0].reason


@pytest.mark.parametrize("shapes", [
    (16384, 4096, 128),    # mellum2_12b_a2_5b: q
    (16384, 512, 128),     # and k
    (8192, 1024, 64),      # keye's indexer qi, kimi's q_r
    (8192, 64, 64),        # keye's indexer ki, kimi's shared k_r
    (512, 256, 256),
], ids=["mellum_q", "mellum_k", "qi_q_r", "ki_k_r", "D256"])
def test_a_tpu_takes_the_cells_rotations(shapes):
    selected, rows = registry.probe("rotary", backend="tpu", shapes=shapes,
                                    dtypes=("bfloat16",))
    assert selected == "pallas", rows
    assert registry.probe("rotary", backend="cpu", shapes=shapes,
                          dtypes=("bfloat16",))[0] == "xla"


REFUSED = {
    "D_off_32": ((1024, 64, 16), ("bfloat16",), (), "multiple of 32"),
    "D_48": ((1024, 96, 48), ("bfloat16",), (), "multiple of 32"),
    "float64": ((1024, 512, 128), ("float64",), (), "bfloat16 or float32"),
    "S_off_the_block": ((1000, 512, 128), ("bfloat16",), (),
                        "position block"),
    "partitioned_over_a_mesh": ((16384, 4096, 128), ("bfloat16",),
                                ((registry.MESH_DEVICES, 4),), "partitioned"),
}


@pytest.mark.parametrize("case", list(REFUSED))
def test_the_registry_refuses_the_pallas_body_with_a_reason(case):
    shapes, dtypes, meta, why = REFUSED[case]
    selected, rows = registry.probe("rotary", backend="tpu", shapes=shapes,
                                    dtypes=dtypes, meta=meta)
    by = {r["impl"]: r for r in rows}
    assert selected == "xla" and by["xla"]["available"]
    assert not by["pallas"]["available"] and why in by["pallas"]["reason"]


def test_the_registry_lists_the_kernel_and_counts_its_dispatches():
    assert "rotary" in registry.kernel_names()
    assert registry.SELECTION_RULES == 7
    assert registry.config_fingerprint()["rotary"] == "auto"
    row = next(r for r in registry.describe(backend="cpu")
               if r["kernel"] == "rotary")
    assert row["impl"] == "xla" and "rope_xla" in row["reason"]

    def count(impl):
        fam = obs.metrics.get_family("dl4j_kernel_dispatch_total")
        return sum(c.get() for c in fam.children()
                   if c.labels == {"kernel": "rotary", "impl": impl})

    before = count("xla")
    dsa.rope(_x((64, 2, 64), "float32"), 1e4)
    assert count("xla") == before + 1
