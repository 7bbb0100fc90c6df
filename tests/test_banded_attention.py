"""The registry's `banded_attention`: the masked flash kernels with no mask
operand (`kernels/flash_attention.py`: a visit list that knows the window,
the tile's mask from iotas), run in interpret mode on the CPU and held to
the XLA row blocks of `nn/layers/dsa.py` and to attention written out
densely, forward and all three gradients; the visit list against a
brute-force count; the rules that choose between the bodies. The chip's
compiler sees the same kernels in `tests/test_chip_compile.py`."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import observability as obs
from deeplearning4j_tpu.kernels import flash_attention as fa
from deeplearning4j_tpu.kernels import registry
from deeplearning4j_tpu.nn.layers import dsa

S, DH = 128, 16
BLOCK_Q, BLOCK_K = 32, 64
# none, smaller than a block, not a multiple of one, a multiple of the
# k block, larger than the sequence
WINDOWS = {"none": None, "w5": 5, "w40": 40, "w64": 64, "w500": 500}


def _band(s, window, causal=True):
    rows, cols = np.arange(s)[:, None], np.arange(s)[None, :]
    keep = cols <= rows if causal else np.ones((s, s), bool)
    return keep if window is None else keep & (cols > rows - window)


def _operands(rng, g, dtype, s=S):
    kv = 1 if g == 8 else 2
    mk = lambda *shape: jnp.asarray(rng.randn(*shape), dtype)
    return (mk(s, kv * g, DH), mk(s, kv, DH), mk(s, kv, DH),
            jnp.asarray(rng.randn(s, kv * g, DH), jnp.float32))


def _value_and_grads(attn, q, k, v, w):
    return attn(q, k, v), jax.grad(
        lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32) * w),
        argnums=(0, 1, 2))(q, k, v)


def _dense(q, k, v, keep):
    g = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(a, g, axis=1) for a in (k, v))
    s = jnp.einsum("thd,shd->hts", q, k,
                   preferred_element_type=jnp.float32) * q.shape[2] ** -0.5
    p = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hts,shd->thd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _close(got, want, dtype):
    got, want = (np.asarray(a.astype(jnp.float32)) for a in (got, want))
    assert np.isfinite(got).all()
    tol = 3e-2 if dtype == "bfloat16" else 2e-5
    np.testing.assert_allclose(got, want, rtol=tol,
                               atol=tol * np.abs(want).max())


def _pallas(window, causal=True, block_q=BLOCK_Q, block_k=BLOCK_K):
    return lambda q, k, v: fa._masked_attention_pallas(
        q, k, v, None, causal, block_q, block_k, True,
        fa.band_window(q.shape[0], window))


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("g", [1, 8], ids=["G1", "G8"])
@pytest.mark.parametrize("window", list(WINDOWS))
def test_pallas_body_matches_the_xla_body_and_the_dense_band(rng, window, g,
                                                             dtype):
    window = WINDOWS[window]
    q, k, v, w = _operands(rng, g, dtype)
    got = _value_and_grads(_pallas(window), q, k, v, w)
    xla = _value_and_grads(
        lambda q, k, v: dsa.banded_gqa_attention_xla(
            q, k, v, window, True, block=32, span=64), q, k, v, w)
    keep = jnp.asarray(_band(S, window))
    dense = _value_and_grads(lambda q, k, v: _dense(q, k, v, keep),
                             q, k, v, w)
    assert got[0].dtype == q.dtype and got[0].shape == q.shape
    for other in (xla, dense):
        _close(got[0], other[0], dtype)
        for a, b, like in zip(got[1], other[1], (q, k, v)):
            assert a.dtype == like.dtype and a.shape == like.shape
            _close(a, b, dtype)


@pytest.mark.parametrize("g", [1, 8], ids=["G1", "G8"])
def test_a_layer_that_is_not_causal_reads_every_key(rng, g):
    q, k, v, w = _operands(rng, g, "float32")
    dense = _value_and_grads(
        lambda q, k, v: _dense(q, k, v, jnp.ones((S, S), bool)), q, k, v, w)
    for attn in (_pallas(None, causal=False),
                 lambda q, k, v: dsa.banded_gqa_attention_xla(
                     q, k, v, None, False, block=32, span=64)):
        o, grads = _value_and_grads(attn, q, k, v, w)
        _close(o, dense[0], "float32")
        for a, b in zip(grads, dense[1]):
            _close(a, b, "float32")
    with pytest.raises(ValueError, match="causal"):
        dsa.banded_gqa_attention(q, k, v, 16, causal=False)


def test_the_windows_edges(rng):
    """Query t reads key t - window + 1 and not key t - window: moving the
    one's value moves row t, moving the other's does not (both bodies)."""
    window, t = 40, 100
    q, k, v, _ = _operands(rng, 8, "float32")
    for attn in (_pallas(window),
                 lambda q, k, v: dsa.banded_gqa_attention_xla(
                     q, k, v, window, block=32, span=64)):
        base = np.asarray(attn(q, k, v))
        inside = np.asarray(attn(q, k, v.at[t - window + 1].add(1e3)))
        outside = np.asarray(attn(q, k, v.at[t - window].add(1e3)))
        assert not np.allclose(inside[t], base[t])
        np.testing.assert_array_equal(outside[t], base[t])
        assert not np.allclose(outside[t - 1], base[t - 1])
        # and nothing after itself
        later = np.asarray(attn(q, k, v.at[t + 1].add(1e3)))
        np.testing.assert_array_equal(later[:t + 1], base[:t + 1])


@pytest.mark.parametrize("order", ["row", "col"])
@pytest.mark.parametrize("block_q,block_k", [(32, 64), (64, 32), (32, 32),
                                             (128, 512)])
@pytest.mark.parametrize("window", [None, 1, 5, 32, 33, 64, 100, 1024])
def test_visit_list_holds_every_tile_that_meets_the_band_and_no_other(
        window, block_q, block_k, order):
    s = 1024 if block_k == 512 else 256
    nq, nk = s // block_q, s // block_k
    keep = _band(s, window)
    want = {(i, j) for i in range(nq) for j in range(nk)
            if keep[i * block_q:(i + 1) * block_q,
                    j * block_k:(j + 1) * block_k].any()}
    ii, jj = fa._pair_arrays(nq, nk, block_q, block_k, True, order,
                             fa.band_window(s, window))
    got = list(zip(ii.tolist(), jj.tolist()))
    assert len(got) == len(set(got)) and set(got) == want
    # row order ends each row at its last k block, column order each column
    # at its last q block: what the kernels' first/last tests compute
    major = got if order == "row" else [(j, i) for i, j in got]
    assert major == sorted(major)
    first, last = {}, {}
    for a, b in major:
        first.setdefault(a, b)
        last[a] = b
    w = fa.band_window(s, window)
    for a in first:
        if order == "row":
            assert first[a] == int(fa._first_k_block(a, block_q, block_k, w))
            assert last[a] == int(fa._last_k_block(a, block_q, block_k, nk,
                                                   True))
        else:
            assert first[a] == (a * block_k) // block_q
            assert last[a] == int(fa._last_q_block(a, block_q, block_k, nq,
                                                   w))


def test_pair_arrays_without_a_window_are_what_they_were():
    for order in ("row", "col"):
        for causal in (True, False):
            a = fa._pair_arrays(8, 4, 32, 64, causal, order)
            b = fa._pair_arrays(8, 4, 32, 64, causal, order, None)
            assert all(np.array_equal(x, y) for x, y in zip(a, b))
    ii, jj = fa._pair_arrays(8, 4, 32, 64, True, "row")
    assert len(ii) == sum(((i + 1) * 32 - 1) // 64 + 1 for i in range(8))
    with pytest.raises(ValueError, match="causal"):
        fa._pair_arrays(8, 4, 32, 64, False, "row", 16)


@pytest.mark.parametrize("s,window,causal", [
    (256, None, True), (256, 40, True), (256, 1, True), (256, 300, True),
    (256, None, False), (16384, 1024, True), (16384, None, True)])
def test_band_pairs_and_fill_share(s, window, causal):
    if s <= 256:
        assert fa.band_pairs(s, window, causal) == int(
            _band(s, window, causal).sum())
    share = fa.band_fill_share(s, 8, 128, 2, window, causal)
    assert 0.0 < share <= 1.0
    if s == 16384:
        # the cell's layers: 128 x 512 tiles; the full layer wastes part of
        # its diagonal tiles, a sliding layer a third of its 3 to 4 tiles a row
        assert fa.masked_blocks(s, 8, 128, 2) == (128, 512)
        assert share == pytest.approx(0.9696 if window is None else 0.6667,
                                      abs=2e-3)


def _dispatches(impl):
    fam = obs.metrics.get_family("dl4j_kernel_dispatch_total")
    return sum(c.get() for c in fam.children()
               if c.labels == {"kernel": "banded_attention", "impl": impl})


def test_auto_off_the_tpu_runs_the_xla_body_and_forced_the_pallas(
        rng, monkeypatch):
    monkeypatch.delenv("DL4J_TPU_KERNELS", raising=False)
    monkeypatch.delenv("DL4J_TPU_KERNEL_BANDED_ATTENTION", raising=False)
    registry.clear_cache()
    q, k, v, _ = _operands(rng, 8, "float32")
    before = _dispatches("xla"), _dispatches("pallas")
    got, fill = dsa.banded_gqa_attention(q, k, v, 40)
    assert (_dispatches("xla"), _dispatches("pallas")) \
        == (before[0] + 1, before[1])
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(dsa.banded_gqa_attention_xla(q, k, v, 40)))
    assert fill == 0.0                  # the XLA body visits no tiles
    res = registry.resolve("banded_attention", shapes=(S, 8, DH, 1),
                           dtypes=("float32",))
    assert res.impl == "xla" and "auto off-TPU" in res.reason
    monkeypatch.setenv("DL4J_TPU_KERNEL_BANDED_ATTENTION", "pallas")
    registry.clear_cache()
    forced, fill = dsa.banded_gqa_attention(q, k, v, 40)
    assert _dispatches("pallas") == before[1] + 1
    assert fill == fa.band_fill_share(S, 8, DH, 4, 40)
    _close(forced, got, "float32")
    registry.clear_cache()


@pytest.mark.parametrize("shapes,dtype,ok,why", [
    ((16384, 32, 128, 4), "bfloat16", True, "no mask operand"),
    ((16384, 32, 128, 4), "float32", True, "no mask operand"),
    ((16384, 32, 128, 4), "float64", False, "float64"),
    ((16400, 32, 128, 4), "bfloat16", False, "S=16400"),
    ((16384, 32, 96, 4), "bfloat16", False, "Dh=96")],
    ids=["published", "f32", "f64", "S-off-tile", "Dh-off-lanes"])
def test_what_the_registry_answers_on_a_tpu(shapes, dtype, ok, why):
    selected, rows = registry.probe("banded_attention", backend="tpu",
                                    shapes=shapes, dtypes=(dtype,))
    pallas = next(r for r in rows if r["impl"] == "pallas")
    assert pallas["available"] is ok and why in pallas["reason"], pallas
    assert selected == ("pallas" if ok else "xla")
