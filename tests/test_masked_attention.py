"""The registry's `masked_attention`: the Pallas flash body with a per-query
mask operand (`kernels/flash_attention.py`), run in interpret mode on the CPU
and held to the XLA row blocks of `nn/layers/dsa.py`, and the rules that
choose between the two. The chip's compiler sees the same kernels in
`tests/test_chip_compile.py`."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import observability as obs
from deeplearning4j_tpu.kernels import flash_attention as fa
from deeplearning4j_tpu.kernels import registry
from deeplearning4j_tpu.nn.layers import dsa

S, DH, TOP_K = 128, 16, 16
BLOCK_Q, BLOCK_K = 32, 64       # 4 x 2 tiles, 6 of them visited


def _top_k_mask(rng, s=S, k=TOP_K):
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)),
                       jnp.asarray(rng.randn(s, s), jnp.float32), -jnp.inf)
    return dsa.select_top_k(scores, k)


def _mask(rng, kind):
    if kind == "causal":
        return jnp.tril(jnp.ones((S, S), bool))
    keep = _top_k_mask(rng)
    if kind == "single_key":
        # row 70 keeps key 5 alone: every later tile of its row is empty
        keep = keep.at[70].set(jnp.arange(S) == 5)
    return keep


def _operands(rng, g, dtype, batch, s=S):
    kv = 1 if g == 8 else 2
    lead = () if batch is None else (batch,)
    mk = lambda *shape: jnp.asarray(rng.randn(*lead, *shape), dtype)
    return (mk(s, kv * g, DH), mk(s, kv, DH), mk(s, kv, DH),
            jnp.asarray(rng.randn(*lead, s, kv * g, DH), jnp.float32))


def _value_and_grads(attn, q, k, v, keep, w):
    def one(q, k, v, keep, w):
        return attn(q, k, v, keep), jax.grad(
            lambda q, k, v: jnp.sum(
                attn(q, k, v, keep).astype(jnp.float32) * w),
            argnums=(0, 1, 2))(q, k, v)

    if q.ndim == 4:   # a batch, as `extended_attention_apply` runs it
        return jax.vmap(one)(q, k, v, keep, w)
    return one(q, k, v, keep, w)


def _pallas(q, k, v, keep, causal=True, block_q=BLOCK_Q, block_k=BLOCK_K):
    return fa._masked_attention_pallas(q, k, v, keep, causal, block_q,
                                       block_k, True)


def _dense(q, k, v, keep):
    """Masked attention written out over the whole `[H, S, S]` scores."""
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
    # bf16: both sides round their products' operands; f32: the blocks sum
    # in another order
    tol = 3e-2 if dtype == "bfloat16" else 2e-5
    np.testing.assert_allclose(got, want, rtol=tol,
                               atol=tol * np.abs(want).max())


@pytest.mark.parametrize("batch", [None, 2], ids=["B1", "B2-vmap"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("g", [1, 8], ids=["G1", "G8"])
@pytest.mark.parametrize("kind", ["causal", "top_k", "single_key"])
def test_pallas_body_matches_the_xla_body(rng, kind, g, dtype, batch):
    q, k, v, w = _operands(rng, g, dtype, batch)
    keep = _mask(rng, kind)
    if batch is not None:
        keep = jnp.stack([keep, _mask(rng, kind)])
    o, grads = _value_and_grads(_pallas, q, k, v, keep, w)
    o_ref, grads_ref = _value_and_grads(dsa.masked_gqa_attention_xla,
                                        q, k, v, keep, w)
    assert o.dtype == q.dtype and o.shape == q.shape
    _close(o, o_ref, dtype)
    for got, want, like in zip(grads, grads_ref, (q, k, v)):
        assert got.dtype == like.dtype and got.shape == like.shape
        _close(got, want, dtype)


@pytest.mark.parametrize("block_k", [256, 512])
def test_score_tiles_wider_than_the_statistics_lanes(rng, block_k):
    """The cell's forward runs 128 x 512 tiles: the running max is tiled
    across the tile's 128-lane columns and the running sum adds them."""
    s = 512
    q, k, v, w = _operands(rng, 8, "float32", None, s)
    keep = _top_k_mask(rng, s, 96)
    attn = lambda *a: _pallas(*a, block_q=128, block_k=block_k)
    o, grads = _value_and_grads(attn, q, k, v, keep, w)
    o_ref, grads_ref = _value_and_grads(dsa.masked_gqa_attention_xla,
                                        q, k, v, keep, w)
    _close(o, o_ref, "float32")
    for got, want in zip(grads, grads_ref):
        _close(got, want, "float32")


def _two_sided_mask(rng, kind):
    if kind == "all_keys":
        return jnp.ones((S, S), bool)
    # about a fifth of the keys on either side of the diagonal; row 40 keeps
    # key 120 alone, which a causal walk never reaches
    keep = jnp.asarray(rng.rand(S, S) < 0.2) | jnp.eye(S, dtype=bool)
    return keep.at[40].set(jnp.arange(S) == 120)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("g", [1, 8], ids=["G1", "G8"])
@pytest.mark.parametrize("kind", ["all_keys", "two_sided"])
def test_a_layer_that_is_not_causal_reads_the_keys_after_a_row(rng, kind, g,
                                                               dtype):
    """`causal=False` (a bidirectional `SelfAttentionLayer`): the Pallas
    body walks every tile and the XLA body's row blocks read every key,
    spans of rows shorter than the sequence included."""
    q, k, v, w = _operands(rng, g, dtype, None)
    keep = _two_sided_mask(rng, kind)
    dense = _value_and_grads(_dense, q, k, v, keep, w)
    bodies = [lambda *a: _pallas(*a, causal=False),
              lambda *a: dsa.masked_gqa_attention_xla(
                  *a, False, block=32, span=64)]
    for attn in bodies:
        o, grads = _value_and_grads(attn, q, k, v, keep, w)
        _close(o, dense[0], dtype)
        for got, want in zip(grads, dense[1]):
            _close(got, want, dtype)


def test_bidirectional_layer_forced_to_the_pallas_body(rng, monkeypatch):
    """`SelfAttentionLayer(n_kv_heads=..., causal=False)` has no indexer, so
    it resolves `banded_attention` and hands the kernel its `causal` and no
    mask: the forced Pallas body gives the XLA body's full attention, output
    and gradient, and position 0 sees the last position's value."""
    from deeplearning4j_tpu.nn.conf.layers import SelfAttentionLayer

    conf = SelfAttentionLayer(n_in=32, n_out=32, n_heads=4, n_kv_heads=2,
                              rope_theta=1e4, causal=False)
    params = {n: jnp.asarray(0.2 * rng.randn(*shape), jnp.float32)
              for n, shape in conf.param_shapes().items()}
    x = jnp.asarray(rng.randn(1, S, 32), jnp.float32)

    def run(x):
        return dsa.extended_attention_apply(conf, params, {}, x)[0]

    def both(impl):
        monkeypatch.setenv("DL4J_TPU_KERNEL_BANDED_ATTENTION", impl)
        registry.clear_cache()
        return run(x), jax.grad(lambda x: jnp.sum(run(x) ** 2))(x)

    before = _dispatches("pallas", "banded_attention")
    masked = _dispatches("pallas") + _dispatches("xla")
    got, want = both("pallas"), both("xla")
    assert _dispatches("pallas", "banded_attention") == before + 2
    assert _dispatches("pallas") + _dispatches("xla") == masked
    for a, b in zip(got, want):
        _close(a, b, "float32")
    assert not np.allclose(np.asarray(run(x.at[0, -1].add(1.0))[0, 0]),
                           np.asarray(got[0][0, 0]))
    registry.clear_cache()


def test_tiles_wholly_masked_for_some_rows_carry_no_weight(rng):
    """Rows 96..127 keep keys 70..90 only, so the tiles of keys 0..63 are
    visited first and hold nothing of theirs: the running max is still
    `_NEG` there and exp(_NEG - _NEG) is 1. Rows 64..95 keep keys 3..20
    only, so their last tile is empty. Neither may put weight on a key that
    was not kept: the output does not move when those keys' values do."""
    q, k, v, w = _operands(rng, 8, "float32", None)
    keep = jnp.tril(jnp.ones((S, S), bool))
    cols = jnp.arange(S)
    keep = keep.at[96:].set((cols >= 70) & (cols <= 90))
    keep = keep.at[64:96].set((cols >= 3) & (cols <= 20))
    o, grads = _value_and_grads(_pallas, q, k, v, keep, w)
    o_ref, grads_ref = _value_and_grads(dsa.masked_gqa_attention_xla,
                                        q, k, v, keep, w)
    _close(o, o_ref, "float32")
    for got, want in zip(grads, grads_ref):
        _close(got, want, "float32")
    # keys 21..63 are kept by rows 21..63 alone
    moved = v.at[21:64].add(1e3)
    o_moved = _pallas(q, k, moved, keep)
    np.testing.assert_array_equal(np.asarray(o_moved[64:]),
                                  np.asarray(o[64:]))
    assert not np.allclose(np.asarray(o_moved[21:64]), np.asarray(o[21:64]))
    # and no gradient reaches a key from a row that did not keep it
    dk, dv = grads[1], grads[2]
    only_late = jax.grad(lambda k, v: jnp.sum(
        _pallas(q, k, v, keep)[64:] * w[64:]), argnums=(0, 1))(k, v)
    for g in only_late:
        g = np.asarray(g)
        assert np.isfinite(g).all()
        assert not g[21:64].any() and not g[91:].any()
        assert g[3:21].any() and g[70:91].any()
    assert np.asarray(dk).any() and np.asarray(dv).any()


def _dispatches(impl, kernel="masked_attention"):
    fam = obs.metrics.get_family("dl4j_kernel_dispatch_total")
    return sum(c.get() for c in fam.children()
               if c.labels == {"kernel": kernel, "impl": impl})


def test_auto_off_the_tpu_runs_the_xla_body_bit_for_bit(rng, monkeypatch):
    monkeypatch.delenv("DL4J_TPU_KERNELS", raising=False)
    monkeypatch.delenv("DL4J_TPU_KERNEL_MASKED_ATTENTION", raising=False)
    registry.clear_cache()
    q, k, v, _ = _operands(rng, 8, "float32", None)
    keep = _mask(rng, "top_k")
    before = _dispatches("xla"), _dispatches("pallas")
    got = dsa.masked_gqa_attention(q, k, v, keep)
    assert (_dispatches("xla"), _dispatches("pallas")) \
        == (before[0] + 1, before[1])
    np.testing.assert_array_equal(
        np.asarray(got),
        np.asarray(dsa.masked_gqa_attention_xla(q, k, v, keep)))
    res = registry.resolve("masked_attention", shapes=(S, 8, DH, 1),
                           dtypes=("float32",))
    assert res.impl == "xla" and "auto off-TPU" in res.reason
    monkeypatch.setenv("DL4J_TPU_KERNELS", "xla")
    registry.clear_cache()
    np.testing.assert_array_equal(
        np.asarray(dsa.masked_gqa_attention(q, k, v, keep)), np.asarray(got))
    registry.clear_cache()


def test_forced_runs_the_pallas_body_at_the_wrappers_blocks(rng, monkeypatch):
    monkeypatch.setenv("DL4J_TPU_KERNEL_MASKED_ATTENTION", "pallas")
    registry.clear_cache()
    q, k, v, _ = _operands(rng, 8, "float32", None)
    keep = _mask(rng, "top_k")
    before = _dispatches("pallas")
    got = dsa.masked_gqa_attention(q, k, v, keep)
    assert _dispatches("pallas") == before + 1
    assert fa.masked_blocks(S, 8, DH, 4) == (128, 128)
    _close(got, dsa.masked_gqa_attention_xla(q, k, v, keep), "float32")
    # an S off the 128-lane tile is refused even when forced, with the reason
    res = registry.resolve("masked_attention", shapes=(96, 8, DH, 1),
                           dtypes=("float32",))
    assert res.impl == "xla" and "S=96" in res.reason
    registry.clear_cache()


@pytest.mark.parametrize("shapes,dtype,ok,why", [
    ((8192, 32, 128, 4), "bfloat16", True, "masked flash kernel"),
    ((8192, 32, 128, 4), "float32", True, "masked flash kernel"),
    ((4096, 8, 64, 8), "bfloat16", True, "masked flash kernel"),
    ((8192, 32, 128, 4), "float64", False, "float64"),
    ((8200, 32, 128, 4), "bfloat16", False, "S=8200"),
    ((8192, 32, 96, 4), "bfloat16", False, "Dh=96"),
    ((8192, 32, 128, 5), "bfloat16", False, "KV=5"),
    ((8192, 128, 128, 1), "bfloat16", False, "G=128"),
    ((8192, 32, 512, 4), "float32", False, "Dh=512"),
], ids=["published", "f32", "Dh64-G1", "f64", "S-off-tile", "Dh-off-lanes",
        "ragged-groups", "group-too-large", "heads-too-wide"])
def test_what_the_registry_answers_on_a_tpu(shapes, dtype, ok, why):
    selected, rows = registry.probe("masked_attention", backend="tpu",
                                    shapes=shapes, dtypes=(dtype,))
    pallas = next(r for r in rows if r["impl"] == "pallas")
    assert pallas["available"] is ok and why in pallas["reason"], pallas
    assert selected == ("pallas" if ok else "xla")


@pytest.mark.parametrize("s,g,dh,itemsize,blocks", [
    (8192, 8, 128, 2, (128, 512)), (8192, 8, 128, 4, (128, 512)),
    (8192, 8, 256, 4, (128, 512)), (8192, 1, 128, 2, (1024, 512)),
    (8192, 4, 128, 2, (256, 512)), (8192, 16, 128, 2, (128, 256)),
    (384, 8, 128, 2, (128, 128)), (1280, 2, 128, 2, (256, 256)),
    (8200, 8, 128, 2, None), (64, 1, 128, 2, None),
    (8192, 32, 128, 2, None), (8192, 8, 512, 4, None)])
def test_blocks_follow_the_sequence_the_group_and_vmem(s, g, dh, itemsize,
                                                       blocks):
    assert fa.masked_blocks(s, g, dh, itemsize) == blocks
    if blocks:
        assert s % blocks[0] == 0 and s % blocks[1] == 0
        assert fa._masked_vmem_bytes(g, *blocks, dh, itemsize) \
            <= fa._MASKED_VMEM_LIMIT


def test_fold_heads_round_trips_and_groups_a_blocks_heads(rng):
    x = jnp.asarray(rng.randn(S, 8, DH), jnp.float32)
    folded = fa._fold_heads(x, 2, BLOCK_Q)
    assert folded.shape == (2, 4 * S, DH)
    np.testing.assert_array_equal(
        np.asarray(fa._unfold_heads(folded, 4, BLOCK_Q)), np.asarray(x))
    # q block 1 of KV head 1: heads 4..7, each its positions 32..63
    block = np.asarray(folded[1, 4 * BLOCK_Q:8 * BLOCK_Q])
    for g in range(4):
        np.testing.assert_array_equal(
            block[g * BLOCK_Q:(g + 1) * BLOCK_Q],
            np.asarray(x[BLOCK_Q:2 * BLOCK_Q, 4 + g]))
