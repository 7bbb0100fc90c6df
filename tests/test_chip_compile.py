"""Described-chip compiles: the main paths' Pallas kernels, at real widths,
through the TPU v5e's own compiler.

Every other kernel test runs the bodies in Pallas interpret mode, which
accepts what the chip's compiler refuses: a block that is the whole operand
(VMEM), a bf16 `sqrt` (a v5e has no bf16 transcendental unit), a block whose
trailing dims are neither whole nor (8, 128)-aligned. The TPU compiler is
installed beside the CPU backend and compiles for a chip that is DESCRIBED,
not attached (`jax.experimental.topologies`), so these tests run on the CPU
sandbox at no chip time. They call the kernels' own `pallas_call` builders
with `interpret=False` and hand them shapes placed on the described device.

What they show: the compiler accepts the kernel at this shape. Nothing
runs, so they say nothing about results or speed — `chip_smoke.py` does.

Only one process at a time may load the TPU's library, so the topology is
described inside a module-scoped fixture (never at import: under xdist every
worker imports every test file), and all of these tests live in this one
file so that one worker runs them.
"""

import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from deeplearning4j_tpu.kernels import bottleneck_block as bb
from deeplearning4j_tpu.kernels import flash_attention as fa
from deeplearning4j_tpu.kernels import fused_update as fu
from deeplearning4j_tpu.kernels import lstm_cell as lc
from deeplearning4j_tpu.kernels import norm_act as na
from deeplearning4j_tpu.kernels import rotary as rot

RESNET50_PARAMS = 25_557_032   # resnet50(n_classes=1000) trainable f32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """`chip(shape, dtype)` -> a ShapeDtypeStruct on the described chip.
    The persistent cache is off around these compiles: an entry written
    for a described device cannot be read back without one, and the next
    compile would warn about it. So is x64, which conftest turns on for
    the gradient checks: the chip runs with 32-bit defaults, and Mosaic
    has no 64-bit index arithmetic to lower a grid's index maps to."""
    from jax.experimental.compilation_cache import compilation_cache

    one = SingleDeviceSharding(topo.devices[0])
    cache_was, x64_was = (jax.config.jax_enable_compilation_cache,
                          jax.config.jax_enable_x64)
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_enable_x64", False)
    compilation_cache.reset_cache()
    yield lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one)
    jax.config.update("jax_enable_x64", x64_was)
    jax.config.update("jax_enable_compilation_cache", cache_was)
    compilation_cache.reset_cache()


def assert_kernel_compiles(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def assert_rotations_under_their_scope(calls, n):
    """`n` of the compiled custom calls' `op_name`s are the registry's
    `rotary` under `attn.rope`, half of them the backward pass's."""
    rotations = [c for c in calls if "rotary" in c]
    assert len(rotations) == n, calls
    assert all("attn.rope" in c for c in rotations), rotations
    assert sum("transpose(" in c for c in rotations) == n // 2, rotations


@pytest.mark.parametrize("kind,hyper", [
    ("nesterovs", (0.9,)), ("adam", (0.9, 0.999, 1e-8))])
def test_fused_update_resnet50(chip, kind, hyper):
    rows = -(-RESNET50_PARAMS // fu._TILE) * 8   # `_to_tiles`' padding
    call = fu._flat_call(kind, rows, hyper, False)
    n_tiled = 3 if kind == "adam" else 2
    assert_kernel_compiles(
        lambda *a: call(*a),
        *([chip((rows, 128))] * n_tiled + [chip((3,))]))
    # The body still compiles, so forcing it works; `auto` declines it for
    # a dispatch with a leaf of a grid block or more (the ravel, PR 29).
    sig = ("tpu", ((RESNET50_PARAMS,),), ("float32",))
    ok, why = fu._pallas_available(*sig, meta=(("kind", kind),), forced=True)
    assert ok, why
    ok, why = fu._pallas_available(*sig, meta=(("kind", kind),))
    assert not ok and "raveled into one flat vector" in why, why


def test_norm_act_batchnorm_relu_bf16_resnet50(chip):
    # ResNet-50 batch 128, stage 1 output: [128*56*56, 256].
    rows, feats = 401408, 256
    call = na._norm_call("batchnorm", rows, feats, 1e-5, "relu", "bfloat16",
                         False)
    vec = chip((1, feats), jnp.bfloat16)
    assert_kernel_compiles(lambda *a: call(*a),
                           chip((rows, feats), jnp.bfloat16), *[vec] * 4)
    # The body still compiles, so forcing it works; `auto` declines it for
    # every BatchNorm, whatever the shape (a fusion barrier, PR 25).
    meta = (("op", "batchnorm"), ("act", "relu"))
    ok, why = na._pallas_available("tpu", (rows, feats), ("bfloat16",),
                                   meta=meta, forced=True)
    assert ok, why
    ok, why = na._pallas_available("tpu", (rows, feats), ("bfloat16",),
                                   meta=meta)
    assert not ok and "fusion barrier" in why, why


@pytest.mark.parametrize("hw,feats", [(56, 256), (7, 2048)],
                         ids=["56x56x256", "7x7x2048"])
def test_auto_leaves_no_custom_call_between_conv_and_batchnorm(
        chip, monkeypatch, hw, feats):
    """conv -> BatchNorm(train) + ReLU -> conv, forward and backward, for
    the described chip as a TPU process would trace it: under `auto` the
    compiled program holds no `tpu_custom_call` (the chain is XLA's to
    fuse), forced it holds the BatchNorm body and moves more bytes."""
    from deeplearning4j_tpu.kernels import registry
    from deeplearning4j_tpu.nn.conf.layers import BatchNormalization
    from deeplearning4j_tpu.nn.layers.normalization import batchnorm_apply

    # The registry asks `jax.default_backend()`, which is the CPU here.
    monkeypatch.setattr(registry, "_default_backend", lambda: "tpu")
    conf = BatchNormalization(n_in=feats, n_out=feats, activation="relu")
    conv = functools.partial(
        jax.lax.conv_general_dilated, window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))

    def loss(w1, w2, gamma, beta, x):
        state = {"mean": jnp.zeros((feats,), jnp.float32),
                 "var": jnp.ones((feats,), jnp.float32)}
        y, _, _ = batchnorm_apply(conf, {"gamma": gamma, "beta": beta},
                                  state, conv(x, w1), train=True)
        return jnp.sum(conv(y, w2).astype(jnp.float32))

    w = chip((1, 1, feats, feats), jnp.bfloat16)
    vec = chip((feats,), jnp.bfloat16)
    args = (w, w, vec, vec, chip((32, hw, hw, feats), jnp.bfloat16))

    def compiled():
        registry.clear_cache()
        exe = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
            *args).compile()
        cost = exe.cost_analysis()
        cost = cost[0] if isinstance(cost, (list, tuple)) else cost
        took = {r.impl for r in registry.resolved() if r.kernel == "norm_act"}
        return exe.as_text(), float(cost["bytes accessed"]), took

    try:
        text, auto_bytes, took = compiled()
        assert took == {"xla"}
        assert "tpu_custom_call" not in text
        monkeypatch.setenv("DL4J_TPU_KERNEL_NORM_ACT", "pallas")
        text, forced_bytes, took = compiled()
        assert took == {"pallas"}
        assert "norm_act_batchnorm" in text and "tpu_custom_call" in text
        assert auto_bytes < forced_bytes
    finally:
        registry.clear_cache()


def test_auto_ravels_no_large_leaf_into_a_flat_vector(chip, monkeypatch):
    """One layer's Adam update at `keye_vl2_30b_a3b`'s MoE signature (75.8 M
    elements, three leaves of 25 M), through the updater's own seam plus the
    engine's `p - delta`, over donated buffers, for the described chip as a
    TPU process would trace it: under `auto` the compiled program holds no
    `tpu_custom_call` and no flat vector of the whole layer, forced it holds
    the body, the vector, and moves more bytes (a count, not a timing)."""
    from deeplearning4j_tpu.kernels import registry
    from deeplearning4j_tpu.ops import updaters

    monkeypatch.setattr(registry, "_default_backend", lambda: "tpu")
    shapes = {"gate_w": (2048, 128), "w_down": (16, 768, 2048),
              "w_gate": (16, 2048, 768), "w_up": (16, 2048, 768)}
    tree = {k: chip(s) for k, s in shapes.items()}
    grads = {k: chip(s, jnp.bfloat16) for k, s in shapes.items()}
    flat = f"f32[{sum(math.prod(s) for s in shapes.values())}]"
    assert flat == "f32[75759616]"
    adam = updaters.adam(0.9, 0.95, 1e-8)

    def step(params, state, g, lr, t):
        g = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), g)
        state, deltas = adam.update(state, g, lr, t)
        return {k: params[k] - deltas[k] for k in params}, state

    def compiled():
        registry.clear_cache()
        # A new function object each time: `jit` would not trace `step`
        # itself again, and the registry is asked while tracing.
        exe = jax.jit(functools.partial(step), donate_argnums=(0, 1)).lower(
            tree, {"m": tree, "v": tree}, grads, chip(()), chip(())).compile()
        cost = exe.cost_analysis()
        cost = cost[0] if isinstance(cost, (list, tuple)) else cost
        took = {r.impl for r in registry.resolved()
                if r.kernel == "fused_update"}
        return exe.as_text(), float(cost["bytes accessed"]), took

    try:
        text, auto_bytes, took = compiled()
        assert took == {"xla"}
        assert "tpu_custom_call" not in text
        assert flat not in text
        monkeypatch.setenv("DL4J_TPU_KERNEL_FUSED_UPDATE", "pallas")
        text, forced_bytes, took = compiled()
        assert took == {"pallas"}
        assert "fused_update_adam" in text and "tpu_custom_call" in text
        assert flat in text
        assert auto_bytes < forced_bytes
    finally:
        registry.clear_cache()


def test_norm_act_layernorm_f32_transformer(chip):
    # transformer_lm d_model=512 at batch 16 x T 1024.
    rows, feats = 16384, 512
    call = na._norm_call("layernorm", rows, feats, 1e-5, "identity",
                         "float32", False)
    vec = chip((1, feats))
    assert_kernel_compiles(lambda *a: call(*a), chip((rows, feats)),
                           vec, vec)
    ok, why = na._pallas_available(
        "tpu", (rows, feats), ("float32",),
        meta=(("op", "layernorm"), ("act", "identity")))
    assert ok, why


def test_norm_act_refuses_features_the_compiler_refuses(chip):
    """Past `_MAX_FEATS` the smallest block no longer fits VMEM: the probe
    says so, with the reason, and the compiler agrees."""
    rows, feats = 1024, 2 * na._MAX_FEATS
    ok, why = na._pallas_available(
        "tpu", (rows, feats), ("float32",),
        meta=(("op", "layernorm"), ("act", "identity")))
    assert not ok and "VMEM" in why
    call = na._norm_call("layernorm", rows, feats, 1e-5, "identity",
                         "float32", False)
    vec = chip((1, feats))
    with pytest.raises(Exception, match="vmem"):
        jax.jit(lambda *a: call(*a)).lower(
            chip((rows, feats)), vec, vec).compile()


def test_lstm_cell_char_rnn(chip):
    b, n = 32, 256
    cell = lc.pallas_cell(b, n, False, False, "tanh", "float32", False)
    assert_kernel_compiles(
        lambda xw, h, c, rw: cell(xw, h, c, rw, None, None),
        chip((b, 4 * n)), chip((b, n)), chip((b, n)), chip((n, 4 * n)))
    ok, why = lc._pallas_available(
        "tpu", (b, n), ("float32",),
        meta=(("gate", "sigmoid"), ("act", "tanh")))
    assert ok, why


def test_bottleneck_block_strided_projecting_inference(chip):
    """`fused_blocks` is opt-in and off the smoke's path, but where its
    probe says yes on a TPU the compiler must too: ResNet-50's first
    stage-2 block (stride 2, projected shortcut) — the stride is taken
    outside the kernel, Mosaic has no strided value slice."""
    b, h, w, cin, f1, f3, sh, sw = 8, 56, 56, 256, 128, 512, 2, 2
    meta = (("train", False), ("project", True), ("act", "relu"),
            ("int8", False))
    ok, why = bb._pallas_available("tpu", (b, h, w, cin, f1, f3, sh, sw),
                                   ("bfloat16",), meta=meta)
    assert ok, why
    ho, wo = h // sh, w // sw
    call = bb._infer_call(b, ho, wo, cin, f1, f3, 1e-5, "relu", True, False,
                          "bfloat16", False)
    args = [chip((b, ho, wo, cin), jnp.bfloat16)]
    for wshape, fo in (((cin, f1), f1), ((3, 3, f1, f1), f1),
                       ((f1, f3), f3), ((cin, f3), f3)):
        args += [chip(wshape, jnp.bfloat16)] + [chip((1, fo))] * 4
    assert_kernel_compiles(lambda *a: call(*a), *args)
    # The stage's first block at f32 needs more VMEM than the budget: no.
    ok, why = bb._pallas_available("tpu", (8, 56, 56, 256, 64, 256, 1, 1),
                                   ("float32",), meta=meta)
    assert not ok and "VMEM" in why


def test_paged_flash_smoke_decode_shape(chip):
    # chip_smoke.py's serve phase: 8 slots, one new token, 8 heads of 64,
    # 64-token pages, 1024-token capacity (16 pages a sequence).
    B, T, H, D, page, NP = 8, 1, 8, 64, 64, 16
    P = B * NP + 1
    assert_kernel_compiles(
        lambda q, k, v, pt, pos: fa._paged_flash(q, k, v, pt, pos,
                                                 causal=True,
                                                 interpret=False),
        chip((B, T, H, D)), chip((P, page, H, D)), chip((P, page, H, D)),
        chip((B, NP), jnp.int32), chip((B,), jnp.int32))
    ok, why = fa._paged_pallas_available(
        "tpu", (B, T, H, D, P, page, NP), ("float32",),
        meta=(("causal", True),))
    assert ok, why


def test_paged_flash_refuses_oversized_blocks():
    """No topology needed: this is the registry's own answer."""
    ok, why = fa._paged_pallas_available(
        "tpu", (8, 1, 32, 128, 65, 256, 8), ("float32",),
        meta=(("causal", True),))
    assert not ok and "VMEM" in why


def test_no_pallas_body_in_a_partitioned_program(topo, chip):
    """Under a mesh of several devices the TPU compiler refuses any Pallas
    body ("Mosaic kernels cannot be automatically partitioned"), so the
    registry answers no for every kernel there — and says why."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from deeplearning4j_tpu.kernels import registry
    from deeplearning4j_tpu.parallel.context import (ParallelContext,
                                                     parallel_context)

    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"))
    rows, feats = 1024, 512
    call = na._norm_call("layernorm", rows, feats, 1e-5, "identity",
                         "float32", False)
    x = jax.ShapeDtypeStruct((rows, feats), jnp.float32,
                             sharding=NamedSharding(mesh, P(None, "model")))
    vec = jax.ShapeDtypeStruct((1, feats), jnp.float32,
                               sharding=NamedSharding(mesh, P()))
    with pytest.raises(Exception, match="automatically partitioned"):
        jax.jit(lambda *a: call(*a)).lower(x, vec, vec).compile()

    with parallel_context(ParallelContext(mesh, model_axis="model")):
        for kernel in registry.kernel_names():
            res = registry.resolve(kernel, backend="tpu")
            assert res.impl == "xla", res
            assert "partitioned over a 4-device mesh" in res.reason, res
    assert registry.resolve("norm_act", backend="tpu").impl == "pallas"


@pytest.mark.parametrize("B,T", [(16, 1024), (2, 8192)],
                         ids=["resident-T1024", "stream-bwd-T8192"])
def test_flash_attention_forward_backward(chip, B, T):
    # T=8192: the forward stays resident, the backward streams (the
    # resident dk/dv kernel's [T, 1] columns no longer fit VMEM there).
    H, D = 8, 64
    flash = functools.partial(fa._flash_attention_pallas, causal=True,
                              scale=None, block_q=256, block_k=256,
                              interpret=False)

    def loss(q, k, v):
        return jnp.sum(flash(q, k, v).astype(jnp.float32))

    x = chip((B, T, H, D), jnp.bfloat16)
    assert_kernel_compiles(jax.grad(loss, argnums=(0, 1, 2)), x, x, x)


def test_sparse_attention_selection_and_attention_at_published_widths(chip):
    """The XLA half of `nn/layers/dsa.py`: the bisection's loop, the tie
    branch and the recomputed row blocks (the body `masked_attention`
    resolves off the TPU, and the Pallas body's parity reference) compile
    forward and backward at Keye-VL-2.0-30B-A3B's head counts (4,096
    positions)."""
    from deeplearning4j_tpu.nn.layers import dsa

    S, H, KV, Dh = 4096, 32, 4, 128

    def loss(q, k, v, scores):
        keep = dsa.select_top_k(scores, 2048)
        o = dsa.masked_gqa_attention_xla(q, k, v, keep)
        return jnp.sum(o.astype(jnp.float32))

    args = (chip((S, H, Dh), jnp.bfloat16), chip((S, KV, Dh), jnp.bfloat16),
            chip((S, KV, Dh), jnp.bfloat16), chip((S, S), jnp.float32))
    exe = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(*args).compile()
    text = exe.as_text()
    assert "tpu_custom_call" not in text
    assert " while(" in text and " conditional(" in text
    m = exe.memory_analysis()
    # no [H, S, S] tensor is kept: 2.1 GB in f32 at these sizes
    assert m.temp_size_in_bytes < 1.2e9


@pytest.mark.parametrize("S,H,KV,Dh,dtype,causal", [
    (8192, 32, 4, 128, jnp.bfloat16, True),
    (8192, 32, 4, 128, jnp.float32, True),
    (8192, 32, 4, 256, jnp.float32, True),
    (2048, 8, 8, 64, jnp.bfloat16, True),
    (2048, 16, 1, 256, jnp.bfloat16, True),
    (4096, 32, 4, 128, jnp.bfloat16, False)],
    ids=["published-bf16", "published-f32", "f32-Dh256", "G1-Dh64",
         "G16-Dh256", "bidirectional"])
def test_masked_attention_forward_backward(chip, S, H, KV, Dh, dtype, causal):
    """The three kernels of `masked_attention` (forward, dq, dk/dv) at the
    blocks the wrapper chooses, for every shape the registry answers yes
    to here; the first is `keye_vl2_30b_a3b.fit_seq8k`'s layer, the last
    walks every tile of a layer that is not causal."""
    ok, why = fa._masked_pallas_available(
        "tpu", (S, H, Dh, KV), (jnp.dtype(dtype).name,))
    assert ok, why
    block_q, block_k = fa.masked_blocks(S, H // KV, Dh,
                                        jnp.dtype(dtype).itemsize)

    def loss(q, k, v, keep):
        o = fa._masked_attention_pallas(q, k, v, keep, causal, block_q,
                                        block_k, False)
        return jnp.sum(o.astype(jnp.float32))

    exe = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        chip((S, H, Dh), dtype), chip((S, KV, Dh), dtype),
        chip((S, KV, Dh), dtype), chip((S, S), jnp.bool_)).compile()
    text = exe.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    # no [H, S, S] tensor: the int8 mask, its transpose and the folded
    # copies of q, o and their cotangents are the largest
    assert exe.memory_analysis().temp_size_in_bytes < 0.9e9


def test_masked_attention_refuses_what_the_compiler_refuses(chip):
    """What `_masked_vmem_bytes` counts over 16 MiB the compiler refuses: a
    score tile twice the wrapper's, and float32 heads of 256 at the 1,024
    keys that heads of 128 take. float64 and an S off the tile never reach
    the compiler (`tests/test_masked_attention.py` has the rest)."""
    S, H, KV = 8192, 32, 4
    for Dh, dtype, block_q, block_k in ((128, jnp.bfloat16, 512, 512),
                                        (256, jnp.float32, 128, 1024)):
        assert fa._masked_vmem_bytes(
            H // KV, block_q, block_k, Dh, jnp.dtype(dtype).itemsize) \
            > fa._MASKED_VMEM_LIMIT

        def loss(q, k, v, keep):
            return jnp.sum(fa._masked_attention_pallas(
                q, k, v, keep, True, block_q, block_k,
                False).astype(jnp.float32))

        with pytest.raises(Exception, match="vmem"):
            jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
                chip((S, H, Dh), dtype), chip((S, KV, Dh), dtype),
                chip((S, KV, Dh), dtype), chip((S, S), jnp.bool_)).compile()
    Dh = 128
    for shapes, dtype in (((S, H, Dh, KV), "float64"),
                          ((S + 8, H, Dh, KV), "bfloat16")):
        ok, _ = fa._masked_pallas_available("tpu", shapes, (dtype,))
        assert not ok


def test_sparse_attention_layer_runs_three_kernels_under_its_scope(
        chip, monkeypatch):
    """`SelfAttentionLayer`'s extended forward at the cell's widths, traced
    as a TPU process would trace it: `masked_attention` resolves `pallas`
    (and the dispatch counter says so), and the compiled gradient holds the
    forward and both backward kernels with `dsa.attend` in their `op_name`,
    which is how `benchmark/harness/scope_time.py` finds their time."""

    from deeplearning4j_tpu import observability as obs
    from deeplearning4j_tpu.kernels import registry
    from deeplearning4j_tpu.nn.conf.layers import SelfAttentionLayer
    from deeplearning4j_tpu.nn.layers import dsa

    # `jax.default_backend()` is the CPU here: steer the registry, and with
    # it `interpret_mode()`, from the test.
    monkeypatch.setattr(registry, "_default_backend", lambda: "tpu")
    monkeypatch.delenv("DL4J_TPU_KERNELS", raising=False)
    monkeypatch.delenv("DL4J_TPU_KERNEL_MASKED_ATTENTION", raising=False)
    registry.clear_cache()
    S, D = 8192, 2048
    conf = SelfAttentionLayer(
        n_in=D, n_out=D, n_heads=32, n_kv_heads=4, head_dim=128,
        rope_theta=1e7, qk_norm_eps=1e-6, causal=True, index_top_k=2048,
        index_n_heads=16, index_head_dim=64)
    bf = jnp.bfloat16
    shapes = conf.param_shapes()
    trained = [n for n in shapes if n not in conf.frozen_param_names()]

    def loss(train, frozen, x):
        out, _, _ = dsa.extended_attention_apply(
            conf, {**train, **frozen}, {}, x)
        return jnp.sum(out.astype(jnp.float32))

    def count(impl):
        fam = obs.metrics.get_family("dl4j_kernel_dispatch_total")
        return sum(c.get() for c in fam.children() if c.labels == {
            "kernel": "masked_attention", "impl": impl})

    before = count("pallas"), count("xla")
    exe = jax.jit(jax.grad(loss)).lower(
        {n: chip(shapes[n], bf) for n in trained},
        {n: chip(shapes[n], bf) for n in conf.frozen_param_names()},
        chip((1, S, D), bf)).compile()
    registry.clear_cache()
    assert (count("pallas"), count("xla")) == (before[0] + 1, before[1])
    calls = [m for m in re.findall(
        r'custom_call_target="tpu_custom_call".*?op_name="([^"]*)"',
        exe.as_text()) if "masked_attention" in m]
    assert len(calls) == 3, calls
    assert all("dsa.attend" in c for c in calls), calls
    assert sum("transpose(" in c for c in calls) == 2, calls


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("window", [1024, None], ids=["window-1024", "full"])
def test_banded_attention_forward_backward(chip, window, dtype):
    """The three kernels of `banded_attention` (the masked kernels with no
    mask operand: forward, dq, dk/dv) at `mellum2_12b_a2_5b.fit_seq16k`'s
    shapes, a sliding layer's visit list and the full layer's; no `[S, S]`
    array is an operand or a temporary."""
    S, H, KV, Dh = 16384, 32, 4, 128
    ok, why = fa._banded_pallas_available(
        "tpu", (S, H, Dh, KV), (jnp.dtype(dtype).name,))
    assert ok, why
    block_q, block_k = fa.masked_blocks(S, H // KV, Dh,
                                        jnp.dtype(dtype).itemsize)

    def loss(q, k, v):
        o = fa._masked_attention_pallas(q, k, v, None, True, block_q,
                                        block_k, False, window)
        return jnp.sum(o.astype(jnp.float32))

    exe = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        chip((S, H, Dh), dtype), chip((S, KV, Dh), dtype),
        chip((S, KV, Dh), dtype)).compile()
    text = exe.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    for name in ("banded_attention_fwd", "banded_attention_dq",
                 "banded_attention_dkv"):
        assert name in text
    assert "masked_attention_dq" not in text
    assert f"[{S},{S}]" not in text
    # the folded copies of q, o and their cotangents, and lse and d_row as
    # `[KV, G*S, 1]` float32 columns, which the chip pads to 128 lanes (0.27
    # GB each); an int8 [S, S] mask and its transpose would be 0.54 GB more
    assert exe.memory_analysis().temp_size_in_bytes \
        < 3 * S * H * Dh * jnp.dtype(dtype).itemsize + 0.6e9


@pytest.mark.parametrize("kind,window,scaling", [
    ("attn.sliding", 1024, None),
    ("attn.full", None, {"rope_type": "yarn", "factor": 16,
                         "original_max_position_embeddings": 8192,
                         "beta_fast": 32, "beta_slow": 1,
                         "attention_factor": 1.2772588722239782})])
def test_windowed_and_full_layers_run_three_kernels_under_their_scope(
        chip, monkeypatch, kind, window, scaling):
    """`SelfAttentionLayer` without an indexer at the cell's widths, traced
    as a TPU process would trace it: `banded_attention` resolves `pallas`,
    the compiled gradient holds the forward and both backward kernels with
    the layer kind's scope in their `op_name` (how `scope_time.py` finds
    their time), the rotary step carries `attn.rope`, and nothing is
    `[S, S]`."""

    from deeplearning4j_tpu import observability as obs
    from deeplearning4j_tpu.kernels import registry
    from deeplearning4j_tpu.nn.conf.layers import SelfAttentionLayer
    from deeplearning4j_tpu.nn.layers import dsa

    monkeypatch.setattr(registry, "_default_backend", lambda: "tpu")
    monkeypatch.delenv("DL4J_TPU_KERNELS", raising=False)
    monkeypatch.delenv("DL4J_TPU_KERNEL_BANDED_ATTENTION", raising=False)
    registry.clear_cache()
    S, D = 16384, 2304
    conf = SelfAttentionLayer(
        n_in=D, n_out=D, n_heads=32, n_kv_heads=4, head_dim=128,
        rope_theta=5e5, qk_norm_eps=1e-6, causal=True, sliding_window=window,
        rope_scaling=scaling)
    bf = jnp.bfloat16

    def loss(params, x):
        out, state, _ = dsa.extended_attention_apply(conf, params, {}, x)
        assert set(state) == {"band_fill_share"}
        return jnp.sum(out.astype(jnp.float32))

    def count(impl):
        fam = obs.metrics.get_family("dl4j_kernel_dispatch_total")
        return sum(c.get() for c in fam.children() if c.labels == {
            "kernel": "banded_attention", "impl": impl})

    before = count("pallas"), count("xla")
    exe = jax.jit(jax.grad(loss)).lower(
        {n: chip(shape, bf) for n, shape in conf.param_shapes().items()},
        chip((1, S, D), bf)).compile()
    registry.clear_cache()
    assert (count("pallas"), count("xla")) == (before[0] + 1, before[1])
    text = exe.as_text()
    calls = re.findall(
        r'custom_call_target="tpu_custom_call".*?op_name="([^"]*)"', text)
    attention = [c for c in calls if "rotary" not in c]
    assert len(attention) == 3, calls
    assert all(kind in c and "banded_attention" in c
               for c in attention), calls
    assert sum("transpose(" in c for c in attention) == 2, calls
    assert_rotations_under_their_scope(calls, 4)
    assert f"[{S},{S}]" not in text


def test_cca_layer_runs_its_kernels_under_its_scopes(chip, monkeypatch):
    """`CompressedConvAttentionLayer` at `zaya1_8b.fit_seq8k`'s widths (8
    query and 2 key/value heads of 128 over 2048, rotary on 64 channels),
    traced as a TPU process would trace it: the core is `banded_attention`'s
    three kernels under `cca.attend`, the rotary step the registry's `rotary`
    on the 64-channel part of q and k under `cca.conv` (forward and
    backward), and nothing is `[S, S]`."""
    from deeplearning4j_tpu.kernels import registry
    from deeplearning4j_tpu.nn.conf.layers import (
        CompressedConvAttentionLayer)
    from deeplearning4j_tpu.nn.layers import cca

    monkeypatch.setattr(registry, "_default_backend", lambda: "tpu")
    for knob in ("DL4J_TPU_KERNELS", "DL4J_TPU_KERNEL_BANDED_ATTENTION",
                 "DL4J_TPU_KERNEL_ROTARY"):
        monkeypatch.delenv(knob, raising=False)
    registry.clear_cache()
    S, D = 8192, 2048
    conf = CompressedConvAttentionLayer(
        n_in=D, n_out=D, n_heads=8, n_kv_heads=2, head_dim=128,
        rope_theta=5e6, partial_rotary_factor=0.5)
    bf = jnp.bfloat16

    def loss(params, x):
        return jnp.sum(cca.cca_apply(conf, params, {}, x)[0].astype(
            jnp.float32))

    shapes = {n: chip(shape, jnp.float32 if n == "temp" else bf)
              for n, shape in conf.param_shapes().items()}
    text = jax.jit(jax.grad(loss)).lower(shapes, chip((1, S, D), bf)) \
        .compile().as_text()
    registry.clear_cache()
    calls = re.findall(
        r'custom_call_target="tpu_custom_call".*?op_name="([^"]*)"', text)
    attention = [c for c in calls if "rotary" not in c]
    assert len(attention) == 3 and all(
        "cca.attend" in c and "banded_attention" in c for c in attention), \
        calls
    rotations = [c for c in calls if "rotary" in c]
    assert len(rotations) == 4 and all("cca.conv" in c for c in rotations)
    assert sum("transpose(" in c for c in rotations) == 2, rotations
    assert f"[{S},{S}]" not in text


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_latent_attention_forward_backward(chip, dtype):
    """The three kernels of `latent_attention` (the masked kernels with a
    second, rotary product into the score tile against a key tile that all
    heads share) at `kimi_vl_a3b.fit_seq8k`'s shapes: 16 heads of 128 + 64
    against values of 128. No operand is padded to 256 and nothing is
    `[S, S]`."""
    S, H, Dn, Dr = 8192, 16, 128, 64
    ok, why = fa._latent_pallas_available(
        "tpu", (S, H, Dn, Dr, Dn), (jnp.dtype(dtype).name,))
    assert ok, why
    block_q, block_k = fa.masked_blocks(S, 1, Dn + Dr,
                                        jnp.dtype(dtype).itemsize)

    def loss(q_n, q_r, k_n, k_r, v):
        o = fa._latent_attention_pallas(q_n, q_r, k_n, k_r, v, block_q,
                                        block_k, False)
        return jnp.sum(o.astype(jnp.float32))

    exe = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        chip((S, H, Dn), dtype), chip((S, H, Dr), dtype),
        chip((S, H, Dn), dtype), chip((S, Dr), dtype),
        chip((S, H, Dn), dtype)).compile()
    text = exe.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    for name in ("latent_attention_fwd", "latent_attention_dq",
                 "latent_attention_dkv"):
        assert name in text
    assert "banded_attention" not in text and f"[{S},{S}]" not in text
    assert f"{S},256]" not in text and f"{S},{H},256]" not in text


def test_latent_attention_layer_runs_three_kernels_under_its_scope(
        chip, monkeypatch):
    """`SelfAttentionLayer` with `kv_lora_rank` at the cell's widths, traced
    as a TPU process would trace it: `latent_attention` resolves `pallas`,
    the compiled gradient holds the forward and both backward kernels with
    `mla.attend` in their `op_name`, the projections carry `mla.project`
    and the rotary step `attn.rope`."""
    from deeplearning4j_tpu import observability as obs
    from deeplearning4j_tpu.kernels import registry
    from deeplearning4j_tpu.nn.conf.layers import SelfAttentionLayer
    from deeplearning4j_tpu.nn.layers import dsa

    monkeypatch.setattr(registry, "_default_backend", lambda: "tpu")
    monkeypatch.delenv("DL4J_TPU_KERNELS", raising=False)
    monkeypatch.delenv("DL4J_TPU_KERNEL_LATENT_ATTENTION", raising=False)
    registry.clear_cache()
    S, D = 8192, 2048
    conf = SelfAttentionLayer(
        n_in=D, n_out=D, n_heads=16, rope_theta=8e5, causal=True,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128)
    bf = jnp.bfloat16

    def loss(params, x):
        out, state, _ = dsa.extended_attention_apply(conf, params, {}, x)
        assert set(state) == {"band_fill_share"}
        return jnp.sum(out.astype(jnp.float32))

    def count(impl):
        fam = obs.metrics.get_family("dl4j_kernel_dispatch_total")
        return sum(c.get() for c in fam.children() if c.labels == {
            "kernel": "latent_attention", "impl": impl})

    before = count("pallas"), count("xla")
    exe = jax.jit(jax.grad(loss)).lower(
        {n: chip(shape, jnp.float32 if n == "gamma_kv" else bf)
         for n, shape in conf.param_shapes().items()},
        chip((1, S, D), bf)).compile()
    registry.clear_cache()
    assert (count("pallas"), count("xla")) == (before[0] + 1, before[1])
    text = exe.as_text()
    calls = re.findall(
        r'custom_call_target="tpu_custom_call".*?op_name="([^"]*)"', text)
    attention = [c for c in calls if "rotary" not in c]
    assert len(attention) == 3, calls
    assert all("mla.attend" in c and "latent_attention" in c
               for c in attention), calls
    assert sum("transpose(" in c for c in attention) == 2, calls
    # q_r and the shared k_r, each forward and backward
    assert_rotations_under_their_scope(calls, 4)
    assert "mla.project" in text
    assert f"[{S},{S}]" not in text


@pytest.mark.parametrize("N,D,F,E,Eh,temporaries", [
    (8192, 2048, 768, 128, 16, 2.5e9),      # keye_vl2_30b_a3b.fit_seq8k
    (16384, 2304, 896, 64, 8, 3.0e9),       # mellum2_12b_a2_5b.fit_seq16k
], ids=["keye_vl2_30b_a3b", "mellum2_12b_a2_5b"])
def test_dropless_experts_at_published_widths(chip, N, D, F, E, Eh,
                                              temporaries):
    """`expert.moe_ffn_dropless` at both language models' points, top-8;
    the grouped products compile for the chip as XLA's ragged dot, which the
    TPU compiler turns into custom calls of its own (not kernels of this
    repo: `pallas_time_share.fit` counts them all the same). No pass is left
    whose result is a select over a whole `[N * top_k, D]` or
    `[N * top_k, F]` array: dead pairs are masked inside the sums over a
    token's slots, `[N, top_k, D]` in, `[N, D]` out (PR 31)."""
    from deeplearning4j_tpu.parallel import expert

    def loss(x, gate_w, w_gate, w_up, w_down):
        y, aux, _, _ = expert.moe_ffn_dropless(
            {"gate_w": gate_w, "w_gate": w_gate, "w_up": w_up,
             "w_down": w_down}, x, top_k=8)
        return jnp.sum(y.astype(jnp.float32)) + aux

    bf = jnp.bfloat16
    args = (chip((N, D), bf), chip((D, E), bf), chip((Eh, D, F), bf),
            chip((Eh, D, F), bf), chip((Eh, F, D), bf))
    exe = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        *args).compile()
    text = exe.as_text()
    assert "ragged-dot" in text
    assert exe.memory_analysis().temp_size_in_bytes < temporaries
    selects = re.findall(
        rf"= \w+\[{N * 8},(?:{D}|{F})\]\S* select\(", text)
    assert not selects, selects


@pytest.mark.parametrize("N,D,F,E,Eh,K,temporaries,tiles", [
    (8192, 2048, 768, 128, 16, 8, 2.5e9, (256,)),   # keye_vl2_30b_a3b
    (16384, 2304, 896, 64, 8, 8, 3.0e9, (256,)),    # mellum2_12b_a2_5b
    (8192, 2048, 1408, 64, 8, 6, 2.5e9, (256,)),    # kimi_vl_a3b
    # zaya1_8b: a [2048, 2048] table outgrows VMEM beside a row tile, so
    # entries 1 and 2 take N in two parts
    (8192, 2048, 2048, 16, 8, 1, 1.0e9, (256, 1024)),
], ids=["keye_vl2_30b_a3b", "mellum2_12b_a2_5b", "kimi_vl_a3b", "zaya1_8b"])
def test_dropless_experts_run_the_grouped_kernel_at_published_widths(
        chip, monkeypatch, N, D, F, E, Eh, K, temporaries, tiles):
    """`expert.moe_ffn_dropless` at the four language models' points, traced
    as a TPU process traces it: the registry's `grouped_matmul` resolves
    `pallas` for all twelve grouped products of the layer, the chip's
    compiler takes every body at the tiles the registry chose, inside the
    16 MiB of VMEM a kernel has without asking for more (VMEM is found
    here, not on the chip), none of
    XLA's `ragged-dot` calls is left, and each call carries `moe.experts`
    in its `op_name`, which is how `moe_time_share.fit` finds its time."""
    from deeplearning4j_tpu import observability as obs
    from deeplearning4j_tpu.kernels import grouped_matmul as gm
    from deeplearning4j_tpu.kernels import registry
    from deeplearning4j_tpu.parallel import expert

    monkeypatch.setattr(registry, "_default_backend", lambda: "tpu")
    monkeypatch.delenv("DL4J_TPU_KERNELS", raising=False)
    monkeypatch.delenv("DL4J_TPU_KERNEL_GROUPED_MATMUL", raising=False)
    registry.clear_cache()

    def loss(x, gate_w, w_gate, w_up, w_down):
        y, aux, _, _ = expert.moe_ffn_dropless(
            {"gate_w": gate_w, "w_gate": w_gate, "w_up": w_up,
             "w_down": w_down}, x, top_k=K)
        return jnp.sum(y.astype(jnp.float32)) + aux

    def count(impl):
        fam = obs.metrics.get_family("dl4j_kernel_dispatch_total")
        return sum(c.get() for c in fam.children() if c.labels == {
            "kernel": "grouped_matmul", "impl": impl})

    bf = jnp.bfloat16
    before = count("pallas"), count("xla")
    # the value too: the gradient alone does not need the forward's products
    exe = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        chip((N, D), bf), chip((D, E), bf), chip((Eh, D, F), bf),
        chip((Eh, D, F), bf), chip((Eh, F, D), bf)).compile()
    registry.clear_cache()
    # a resolution a product: 3 forward, 3 recomputed, 3 + 3 backward
    assert (count("pallas"), count("xla")) == (before[0] + 12, before[1])
    text = exe.as_text()
    assert "ragged-dot" not in text
    calls = [m for m in re.findall(
        r'custom_call_target="tpu_custom_call".*?op_name="([^"]*)"', text)
        if "grouped_matmul" in m]
    assert len(calls) == 12 and all("moe.experts" in c for c in calls), calls
    kinds = [c.split("/")[-2] for c in calls]
    assert sorted(kinds) == sorted(
        ["grouped_matmul_rows_table"] * 6 + ["grouped_matmul_rows_table_t"] * 3
        + ["grouped_matmul_contracted"] * 3), kinds
    assert exe.memory_analysis().temp_size_in_bytes < temporaries
    # the three transposed copies of the tables are gone with XLA's calls
    assert not re.findall(rf"= bf16\[{Eh},{F},{D}\]\S* transpose\(", text)
    assert "vmem_limit_bytes" not in text
    used = [int(n) for n in re.findall(
        r'grouped_matmul[\w.]* = .*?"used_scoped_memory_configs":\[\{'
        r'"memory_space":"1","offset":"0","size":"(\d+)"', text)]
    assert len(used) == 12 and max(used) <= 16 << 20, used
    assert gm.tiling("rows_table", N * K, D, F, 2) == tiles


# Every rotation of the three language-model cells: q and k of a layer, the
# indexer's qi and ki, a latent layer's q_r and shared k_r (`[S, L, D]`, L
# the elements of a position).
ROTATIONS = {
    "mellum2_12b_a2_5b.q": (16384, 4096, 128), "mellum2_12b_a2_5b.k":
    (16384, 512, 128), "keye_vl2_30b_a3b.q": (8192, 4096, 128),
    "keye_vl2_30b_a3b.k": (8192, 512, 128), "keye_vl2_30b_a3b.qi":
    (8192, 1024, 64), "keye_vl2_30b_a3b.ki": (8192, 64, 64),
    "kimi_vl_a3b.q_r": (8192, 1024, 64), "kimi_vl_a3b.k_r": (8192, 64, 64),
}


@pytest.mark.parametrize("cell", list(ROTATIONS))
def test_rotary_forward_backward_at_the_cells_shapes(chip, cell):
    """The registry's `rotary`, both directions, at every rotation of the
    three language-model cells: the chip's compiler takes each body inside
    the 16 MiB of VMEM a kernel has without asking for more."""
    S, L, D = ROTATIONS[cell]
    ok, why = rot._pallas_available("tpu", (S, L, D), ("bfloat16",))
    assert ok, why

    def both(xt, g, c, s):
        return (rot.rotary_pallas(xt, c, s, D=D),
                rot.rotary_pallas(g, c, s, D=D, transpose=True))

    text = jax.jit(both).lower(
        chip((L, S), jnp.bfloat16), chip((L, S), jnp.bfloat16),
        chip((D // 2, S)), chip((D // 2, S))).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "vmem_limit_bytes" not in text
    used = [int(n) for n in re.findall(
        r'rotary[\w.]* = .*?"used_scoped_memory_configs":\[\{'
        r'"memory_space":"1","offset":"0","size":"(\d+)"', text)]
    assert len(used) == 2 and max(used) <= 16 << 20, used


def test_rotary_leaves_no_float32_halves_and_no_concatenate(chip,
                                                            monkeypatch):
    """`dsa.rope` of `[16384, 32, 128]` bf16 under YaRN, forward and
    backward, traced as a TPU process would trace it: the two kernels and
    no `f32[..., 64]` half of a head, no `concatenate` (XLA's form of the
    same rotation has both, each way)."""
    from deeplearning4j_tpu.kernels import registry
    from deeplearning4j_tpu.nn.layers import dsa

    monkeypatch.setattr(registry, "_default_backend", lambda: "tpu")
    monkeypatch.delenv("DL4J_TPU_KERNELS", raising=False)
    monkeypatch.delenv("DL4J_TPU_KERNEL_ROTARY", raising=False)
    registry.clear_cache()
    yarn = {"rope_type": "yarn", "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782}

    def f(q, g):
        y, vjp = jax.vjp(lambda q: dsa.rope(q, 5e5, yarn), q)
        return y, vjp(g)[0]

    q = chip((16384, 32, 128), jnp.bfloat16)
    text = jax.jit(f).lower(q, q).compile().as_text()
    registry.clear_cache()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert not re.findall(r"f32\[[\d,]*,64\]", text)
    assert "concatenate(" not in text


@pytest.mark.parametrize("cell", ["mellum2_12b_a2_5b", "kimi_vl_a3b"])
def test_a_layer_moves_fewer_bytes_with_the_rotary_kernel(chip, monkeypatch,
                                                          cell):
    """An attention layer's gradient at the cell's widths, its rotations in
    XLA and then in the kernel: the kernel takes its operand in the layout
    the layer's producers write (positions minor), so the compiled program
    moves at least an eighth fewer bytes (9.29 -> 7.33 GB for mellum's
    layer, 4.41 -> 2.99 for kimi's, when this was written)."""
    from deeplearning4j_tpu.kernels import registry
    from deeplearning4j_tpu.nn.conf.layers import SelfAttentionLayer
    from deeplearning4j_tpu.nn.layers import dsa

    monkeypatch.setattr(registry, "_default_backend", lambda: "tpu")
    monkeypatch.delenv("DL4J_TPU_KERNELS", raising=False)
    S, D, kw = {
        "mellum2_12b_a2_5b": (16384, 2304, dict(
            n_heads=32, n_kv_heads=4, head_dim=128, rope_theta=5e5,
            qk_norm_eps=1e-6, sliding_window=1024)),
        "kimi_vl_a3b": (8192, 2048, dict(
            n_heads=16, rope_theta=8e5, kv_lora_rank=512,
            qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128)),
    }[cell]
    conf = SelfAttentionLayer(n_in=D, n_out=D, causal=True, **kw)

    def loss(params, x):
        out, _, _ = dsa.extended_attention_apply(conf, params, {}, x)
        return jnp.sum(out.astype(jnp.float32))

    def gb(mode):
        monkeypatch.setenv("DL4J_TPU_KERNEL_ROTARY", mode)
        registry.clear_cache()
        exe = jax.jit(jax.grad(loss)).lower(
            {n: chip(shape, jnp.float32 if n.startswith("gamma")
                     else jnp.bfloat16)
             for n, shape in conf.param_shapes().items()},
            chip((1, S, D), jnp.bfloat16)).compile()
        cost = exe.cost_analysis()
        cost = cost[0] if isinstance(cost, (list, tuple)) else cost
        calls = re.findall(
            r'custom_call_target="tpu_custom_call".*?op_name="([^"]*)"',
            exe.as_text())
        return cost["bytes accessed"], sum("rotary" in c for c in calls)

    (xla, none), (kernel, some) = gb("xla"), gb("pallas")
    registry.clear_cache()
    assert none == 0 and some > 0
    assert kernel < 0.875 * xla, (kernel / 1e9, xla / 1e9)
