"""`kernels/grouped_matmul.py`: the Pallas bodies, interpreted on the CPU at
small shapes, against `jax.lax.ragged_dot` / `ragged_dot_general`; the visit
list against a loop; the registry's rules.

Each parameter set is a fault a grouped kernel is known to have: an edge
inside a row tile, a group of no rows (first, in the middle, last), one
group over several tiles, a live prefix shorter than M with NaN past it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.kernels import grouped_matmul as gm
from deeplearning4j_tpu.kernels import registry

M, K, N, TM = 1024, 256, 128, 128

# group sizes over M = 1,024 rows in tiles of 128
SIZES = {
    "unaligned_edges": [130, 126, 3, 500, 100, 165],
    "empty_first_middle_last": [0, 300, 0, 0, 411, 0],
    "one_group_over_many_tiles": [5, 900, 7],
    "short_prefix": [70, 0, 200, 33],
    "aligned": [128, 256, 128, 512],
    "one_row": [0, 0, 1, 0],
    "nothing_held": [0, 0, 0],
}
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def _operands(sizes, dtype, seed=0, N=N):
    """rows [M, K], ct [M, N], tables [G, K, N] and [G, N, K]; the rows past
    the live prefix NaN in both `[M, ...]` operands."""
    rng = np.random.default_rng(seed)
    G, held = len(sizes), sum(sizes)

    def mk(*shape):
        return jnp.asarray(rng.standard_normal(shape, np.float32), dtype)

    rows, ct = mk(M, K), mk(M, N)
    dead = (jnp.arange(M) >= held)[:, None]
    return dict(rows=rows, ct=ct, table=mk(G, K, N), table_t=mk(G, N, K),
                rows_nan=jnp.where(dead, jnp.nan, rows),
                ct_nan=jnp.where(dead, jnp.nan, ct),
                sizes=jnp.asarray(sizes, jnp.int32), held=held)


def _close(got, want, dtype):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert np.all(np.isfinite(got))
    # float32: another order of the sums; bfloat16: one rounding of a
    # float32 sum that differs in its last bits is one ulp (2^-8 relative)
    tol = 1e-5 if dtype == "float32" else 2.0 ** -7
    scale = max(1.0, float(np.max(np.abs(want)))) if want.size else 1.0
    assert float(np.max(np.abs(got - want), initial=0.0)) <= tol * scale


@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("case", list(SIZES))
@pytest.mark.parametrize("transposed", [False, True], ids=["table", "table_t"])
@pytest.mark.parametrize("tn", [None, 128], ids=["whole", "in_parts"])
def test_rows_by_group_against_a_table(case, dtype, transposed, tn):
    """Each group's `[K, N]` whole, or a `[K, tn]` part of N at a time (the
    parts an outer grid axis: 2N // 128 = 2 parts here)."""
    o = _operands(SIZES[case], DTYPES[dtype], N=2 * N)
    table = o["table_t"] if transposed else o["table"]
    want = jax.lax.ragged_dot(
        o["rows"], jnp.swapaxes(table, 1, 2) if transposed else table,
        o["sizes"])
    got = gm.rows_table_pallas(o["rows_nan"], table, o["sizes"], tm=TM,
                               tn=tn, transposed=transposed, interpret=True)
    assert got.shape == (M, 2 * N) and got.dtype == want.dtype
    _close(got[:o["held"]], want[:o["held"]], dtype)


@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("case", list(SIZES))
@pytest.mark.parametrize("parts", [(K, N), (128, 128)],
                         ids=["whole", "in_parts"])
def test_a_tables_gradient_by_group(case, dtype, parts):
    """Each group's `[K, N]` whole, or an `[ta, tb]` part at a time (the
    visits run once a part: K // 128 = 2 parts here)."""
    o = _operands(SIZES[case], DTYPES[dtype])
    want = jax.lax.ragged_dot_general(o["rows"], o["ct"], o["sizes"],
                                      gm.GROUPS_CONTRACTED)
    got = gm.contracted_pallas(o["rows_nan"], o["ct_nan"], o["sizes"], tm=TM,
                               ta=parts[0], tb=parts[1], interpret=True)
    assert got.shape == (len(SIZES[case]), K, N) and got.dtype == want.dtype
    _close(got, want, dtype)
    # a group of no rows: written, as zeros, not left as the buffer was
    for g, size in enumerate(SIZES[case]):
        if size == 0:
            assert not np.any(np.asarray(got[g], np.float32)), g


@pytest.mark.parametrize("empty_groups", [False, True])
@pytest.mark.parametrize("case", list(SIZES))
def test_the_visit_list_covers_each_groups_tiles_once_in_order(
        case, empty_groups):
    sizes = SIZES[case]
    offsets, group, tile, count = (np.asarray(a) for a in gm._visits(
        jnp.asarray(sizes, jnp.int32), M, TM, empty_groups))
    want, start = [], 0
    for g, size in enumerate(sizes):
        if size:
            want += [(g, t) for t in range(start // TM,
                                           (start + size - 1) // TM + 1)]
        elif empty_groups:
            want.append((g, min(start // TM, M // TM - 1)))
        start += size
    assert offsets.tolist() == np.cumsum([0] + sizes).tolist()
    assert int(count) == max(len(want), 1)
    assert list(zip(group.tolist(), tile.tolist()))[:len(want)] == want
    assert len(group) == len(tile) == M // TM + len(sizes) - 1
    assert group.dtype == tile.dtype == offsets.dtype == np.int32
    # tiles never go back, and every entry, visited or not, is in range
    assert np.all(np.diff(tile[:len(want)]) >= 0)
    assert 0 <= tile.min() and tile.max() < M // TM
    assert 0 <= group.min() and group.max() < len(sizes)


@pytest.mark.parametrize("mode,impl", [("auto", "xla"), ("xla", "xla"),
                                       ("pallas", "pallas")])
def test_the_entry_points_resolve_through_the_registry(mode, impl,
                                                       monkeypatch):
    """`auto` on the CPU is XLA's lowering, to the call; forced, the three
    entry points run the Pallas bodies and agree with it."""
    monkeypatch.setenv("DL4J_TPU_KERNEL_GROUPED_MATMUL", mode)
    registry.clear_cache()
    o = _operands(SIZES["unaligned_edges"], jnp.float32)
    before = _dispatches()
    held = o["held"]
    one = gm.rows_table(o["rows"], o["table"], o["sizes"])
    t = gm.transposed(o["table_t"], M, o["rows"].dtype)
    back = gm.rows_table_t(o["rows"], t, o["sizes"])
    dw = gm.contracted(o["rows"], o["ct"], o["sizes"])
    after = _dispatches()
    assert after.pop(impl) - before.get(impl, 0) == 3
    assert after == {k: v for k, v in before.items() if k != impl}
    assert (t.tm is None) == (impl == "xla")
    assert t.table.shape == ((len(SIZES["unaligned_edges"]), K, N)
                             if impl == "xla" else o["table_t"].shape)
    want = jax.lax.ragged_dot(o["rows"], o["table"], o["sizes"])
    _close(one[:held], want[:held], "float32")
    _close(back[:held], jax.lax.ragged_dot(o["rows"], jnp.swapaxes(
        o["table_t"], 1, 2), o["sizes"])[:held], "float32")
    _close(dw, jax.lax.ragged_dot_general(
        o["rows"], o["ct"], o["sizes"], gm.GROUPS_CONTRACTED), "float32")
    if impl == "xla":
        assert np.array_equal(np.asarray(one[:held]), np.asarray(want[:held]))
    registry.clear_cache()


def _dispatches():
    from deeplearning4j_tpu import observability as obs

    found = {}
    for line in obs.metrics.to_prometheus().splitlines():
        if line.startswith('dl4j_kernel_dispatch_total{kernel="grouped_matmul"'):
            found[line.split('impl="')[1].split('"')[0]] = float(
                line.rsplit(" ", 1)[1])
    return found


REFUSED = {
    "K_off_the_lane_grid": ((1024, 200, 128, 4), ("bfloat16",) * 2, (),
                            "128-lane grid"),
    "N_off_the_lane_grid": ((1024, 256, 72, 4), ("bfloat16",) * 2, (),
                            "128-lane grid"),
    "float64": ((1024, 256, 128, 4), ("float64",) * 2, (), "bfloat16 or"),
    "mixed_dtypes": ((1024, 256, 128, 4), ("bfloat16", "float32"), (),
                     "both operands"),
    "under_four_row_tiles": ((384, 256, 128, 4), ("bfloat16",) * 2, (),
                             "row tiles"),
    "M_off_the_row_grid": ((1000, 256, 128, 4), ("bfloat16",) * 2, (),
                           "row tiles"),
    # two [128, K] row tiles and a [K, 128] part of N outgrow 16 MiB: no
    # split of N serves (a [4096, 4096] table is taken in parts since the
    # split exists)
    "a_table_past_VMEM": ((131072, 16384, 4096, 8), ("bfloat16",) * 2,
                          (("entry", "rows_table_t"),), "VMEM"),
    "partitioned_over_a_mesh": ((131072, 2304, 896, 8), ("bfloat16",) * 2,
                                ((registry.MESH_DEVICES, 4),), "partitioned"),
}


@pytest.mark.parametrize("case", list(REFUSED))
def test_the_registry_refuses_the_pallas_body_with_a_reason(case):
    shapes, dtypes, meta, why = REFUSED[case]
    selected, rows = registry.probe("grouped_matmul", backend="tpu",
                                    shapes=shapes, dtypes=dtypes, meta=meta)
    by = {r["impl"]: r for r in rows}
    assert selected == "xla" and by["xla"]["available"]
    assert not by["pallas"]["available"] and why in by["pallas"]["reason"]


@pytest.mark.parametrize("shapes,entry,tiles", [
    ((131072, 2304, 896, 8), "rows_table", (256,)),     # mellum2_12b_a2_5b
    ((131072, 896, 2304, 8), "contracted", (512, 896, 1152)),
    ((49152, 2048, 1408, 8), "rows_table_t", (256,)),   # kimi_vl_a3b
    ((49152, 1408, 2048, 8), "rows_table", (128,)),
    ((49152, 2048, 1408, 8), "contracted", (256, 1024, 1408)),
    ((65536, 2048, 768, 16), "contracted", (128, 2048, 768)),   # keye_vl2
    ((131072, 4096, 4096, 8), "contracted", (512, 1024, 1024)),
    ((640, 256, 128, 4), "rows_table", (128,)),
    # zaya1_8b: the whole [2048, 2048] table does not fit beside a row
    # tile, so N goes in two parts
    ((8192, 2048, 2048, 8), "rows_table", (256, 1024)),
    ((8192, 2048, 2048, 8), "rows_table_t", (256, 1024)),
    ((8192, 2048, 2048, 8), "contracted", (512, 1024, 1024)),
    ((131072, 4096, 4096, 8), "rows_table_t", (256, 512)),
])
def test_the_registry_takes_the_cells_signatures_on_a_tpu(shapes, entry,
                                                          tiles):
    meta = (("entry", entry),)
    selected, rows = registry.probe(
        "grouped_matmul", backend="tpu", shapes=shapes,
        dtypes=("bfloat16", "bfloat16"), meta=meta)
    assert selected == "pallas", rows
    M_, K_, N_, _ = shapes
    assert gm.tiling(entry, M_, K_, N_, 2) == tiles
    # and off the TPU under `auto` nothing but XLA's lowering
    assert registry.probe("grouped_matmul", backend="cpu", shapes=shapes,
                          dtypes=("bfloat16", "bfloat16"),
                          meta=meta)[0] == "xla"


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_the_split_body_stays_inside_vmem(dtype):
    """Every `(tm, tn)` the split gives holds its blocks, twice, with the
    float32 product beside them, inside the 16 MiB a kernel has by default;
    and a split is taken only where the whole matrix fits beside no row
    tile."""
    size = jnp.dtype(dtype).itemsize
    for K_ in (1024, 2048, 4096):
        for N_ in (1024, 2048, 4096, 8192):
            tiles = gm.tiling("rows_table", 131072, K_, N_, size)
            assert tiles is not None, (K_, N_)
            tm, tn = (*tiles, N_) if len(tiles) == 1 else tiles
            assert N_ % tn == 0 and tn % 128 == 0
            assert (2 * tm * K_ + 2 * K_ * tn + 2 * tm * tn) * size + (
                tm * tn * 4) <= gm._VMEM_BUDGET
            whole_fits = (2 * 128 * K_ + 2 * K_ * N_ + 2 * 128 * N_) * size + (
                128 * N_ * 4) <= gm._VMEM_BUDGET
            assert (len(tiles) == 1) == whole_fits, (K_, N_, tiles)


def test_zayas_expert_products_resolve_the_kernel_on_a_tpu(monkeypatch):
    """At `zaya1_8b.fit_seq8k`'s widths (8,192 pairs top-1 over 8 held
    experts of K = N = 2,048, bf16), traced as a TPU process traces it:
    all three entries resolve `pallas`, and `transposed` hands the table on
    as stored (no `[G, K, N]` copy) with the split's tiles."""
    monkeypatch.setattr(registry, "_default_backend", lambda: "tpu")
    monkeypatch.delenv("DL4J_TPU_KERNELS", raising=False)
    monkeypatch.delenv("DL4J_TPU_KERNEL_GROUPED_MATMUL", raising=False)
    registry.clear_cache()
    try:
        for entry in ("rows_table", "rows_table_t", "contracted"):
            res = registry.resolve(
                "grouped_matmul", shapes=(8192, 2048, 2048, 8),
                dtypes=("bfloat16", "bfloat16"), meta=(("entry", entry),))
            assert res.impl == "pallas", (entry, res)
        bf = jnp.dtype(jnp.bfloat16)
        table = jax.ShapeDtypeStruct((8, 2048, 2048), bf)
        t = gm.transposed(table, 8192, bf)
        assert t.table is table and (t.tm, t.tn) == (256, 1024)
    finally:
        registry.clear_cache()


def test_the_registry_lists_the_kernel_and_its_rules():
    assert "grouped_matmul" in registry.kernel_names()
    assert registry.SELECTION_RULES >= 8
    assert registry.config_fingerprint()["grouped_matmul"] == "auto"
    row = next(r for r in registry.describe(backend="cpu")
               if r["kernel"] == "grouped_matmul")
    assert row["impl"] == "xla" and "ragged_dot" in row["reason"]
