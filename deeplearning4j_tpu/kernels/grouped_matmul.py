"""Grouped products over rows sorted by group: the dropless experts' twelve.

`parallel/expert.py::_held_experts` keeps its `[M, ...]` arrays in expert
order: the rows of group 0, then of group 1, ..., `group_sizes[g]` rows a
group (a traced vector), and past `sum(group_sizes)` rows that belong to no
group held here. A layer multiplies them twelve times, through three entry
points:

1. `rows_table(rows [M, K], table [G, K, N]) -> [M, N]`: each row times its
   group's matrix (`g`, `u`, `out`).
2. `rows_table_t(rows [M, K], transposed(table [G, N, K])) -> [M, N]`: the
   same against each matrix's transpose (`dhmid`, `drows`).
3. `contracted(rows [M, A], ct [M, B]) -> [G, A, B]`: each group's rows
   contracted, a table's gradient.

The contract, whichever candidate runs: rows past `sum(group_sizes)` are
never read (NaN there is harmless) and the result's rows there are left as
the buffer was; a group of no rows is legal and its `[A, B]` of entry 3 is
zeros; group edges need no alignment; `group_sizes` is traced, so nothing
recompiles when the router moves. Products accumulate in float32 and are
cast once, to the operands' dtype.

The XLA candidate is `jax.lax.ragged_dot` / `ragged_dot_general`, what the
experts called before this module existed (entry 2 on a transposed copy of
the table, which `transposed` makes where the copies always were). On a v5e
XLA's lowering of them reaches 18-49% of the chip's peak at the benchmark's
shapes (PERF.md PR 35).

The Pallas candidate walks a visit list built from `group_sizes` on the
device (`_visits`; the scheme of megablox, Gale et al. 2022): one grid step
a (row tile, group) pair that share a row, in row order, so a tile that
straddles a group's edge is visited once a group under a row mask, and the
grid's bound is the number of such pairs: a tile past the live prefix is
never visited. The lists travel as scalar-prefetch operands and choose each
step's blocks. Entries 1 and 2 tile neither K nor N: a step holds `[tm, K]`
rows, the group's whole `[K, N]` matrix (fetched again only when the group
changes) and a `[tm, N]` result. Entry 3 holds two row tiles and an
`[ta, tb]` part of the group's float32 accumulator, and runs the visits
once a part.

**Every body stays inside the 16 MiB of VMEM a kernel has by default**
(`tiling`; the registry's probe refuses a signature no tiling serves).
Whole `[A, B]` accumulators and two tables a pass, with the limit raised
(`vmem_limit_bytes`, 18-29 MB in use), were as fast alone and wrong in a
program: in the train step of `kimi_vl_a3b.fit_seq8k`, whose kernels took
27-29 MB, every leaf's update stood 2-10 times further from the
reference's than under XLA's lowering, forward and backward, while the same
kernels alone and in the gradient-only program were right to the bit; with
XLA's own reservation raised to the kernels' for every operation
(`--xla_tpu_scoped_vmem_limit_kib`) the step read as under XLA's lowering
again (PERF.md PR 35). XLA keeps arrays of its own in VMEM across a custom
call (the visit lists among them), and what it sets aside for one that asks
for more than the default does not cover what the kernel takes. Do not
raise the limit here without that check's `update_rel` beside it.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from deeplearning4j_tpu.kernels import registry as _registry

GROUPS_CONTRACTED = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((0,), (0,)), ((), ())),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])

# What `tiling` lets a step's blocks take of the 16 MiB of VMEM a kernel
# has without asking for more (see the module's docstring for why not).
_VMEM_BUDGET = 16 << 20
_MIN_ROW_TILES = 4


def _visits(group_sizes, M, tm, empty_groups):
    """The visit list: `(offsets [G + 1], group [V], tile [V], count)`, int32.

    Visit `i < count` multiplies row tile `tile[i]` (`tm` rows) for group
    `group[i]`, whose rows are `offsets[g] <= row < offsets[g + 1]`. A group
    is visited once for every tile it has a row in, groups in order, so the
    tiles never go back and a tile two groups share is visited twice in a
    row. With `empty_groups` a group of no rows gets one visit (entry 3 has
    its zeros to write), else none. `V = M // tm + G - 1` bounds the count;
    entries past `count` are in range and never visited."""
    G, tiles = group_sizes.shape[0], M // tm
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes, dtype=jnp.int32)
    starts = ends - sizes
    n = jnp.where(sizes > 0, (ends + tm - 1) // tm - starts // tm,
                  1 if empty_groups else 0).astype(jnp.int32)
    upto = jnp.cumsum(n, dtype=jnp.int32)
    i = jnp.arange(tiles + G - 1, dtype=jnp.int32)
    group = jnp.minimum(jnp.searchsorted(upto, i, side="right"),
                        G - 1).astype(jnp.int32)
    tile = starts[group] // tm + i - (upto[group] - n[group])
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    # At least one step: a grid of none would leave the kernel out, and a
    # visit of a group of no rows writes nothing it should not.
    return (offsets, group, jnp.clip(tile, 0, tiles - 1).astype(jnp.int32),
            jnp.maximum(upto[-1], 1))


def _rows_of_group(offsets, group, tile, tm, axis=0):
    """This step's group (the visits are the grid's `axis`), its `[tm, 1]`
    row mask and whether the tile lies wholly inside the group."""
    g = group[pl.program_id(axis)]
    start, end = offsets[g], offsets[g + 1]
    low = tile[pl.program_id(axis)] * tm
    row = low + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    return g, (row >= start) & (row < end), (low >= start) & (low + tm <= end)


def _rows_table_kernel(offsets, group, tile, rows, table, out, *, tm,
                       transposed):
    _, mine, _ = _rows_of_group(offsets, group, tile, tm)
    acc = jax.lax.dot_general(
        rows[...], table[...], (((1,), (1 if transposed else 0,)), ((), ())),
        preferred_element_type=jnp.float32)
    # The other rows of the tile are another group's, written by the visit
    # before or after this one, or nobody's.
    out[...] = jnp.where(mine, acc, out[...].astype(jnp.float32)).astype(
        out.dtype)


def _contracted_kernel(offsets, group, tile, rows, ct, out, acc, *, tm):
    i, last = pl.program_id(2), pl.num_programs(2) - 1
    g, mine, inside = _rows_of_group(offsets, group, tile, tm, axis=2)
    dims = (((0,), (0,)), ((), ()))

    @pl.when((i == 0) | (group[jnp.maximum(i - 1, 0)] != g))
    def _():
        acc[...] = jnp.zeros_like(acc)

    @pl.when(inside)
    def _():
        acc[...] += jax.lax.dot_general(rows[...], ct[...], dims,
                                        preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_not(inside))
    def _():
        # Both operands: a row of another group, or of nobody (NaN), must
        # meet a zero on neither side.
        acc[...] += jax.lax.dot_general(
            jnp.where(mine, rows[...], jnp.zeros_like(rows)),
            jnp.where(mine, ct[...], jnp.zeros_like(ct)), dims,
            preferred_element_type=jnp.float32)

    @pl.when((i == last) | (group[jnp.minimum(i + 1, last)] != g))
    def _():
        out[...] = acc[...].astype(out.dtype)


# Jitted, so that a program which makes one product many times (a layer's
# forward pass and its recomputation, every layer of a model) traces and
# lowers each kernel once: 48 bodies a step added 1.3-2 s to every set-up.
@functools.partial(jax.jit, static_argnames=("tm", "transposed", "interpret"))
def rows_table_pallas(rows, table, group_sizes, *, tm, transposed=False,
                      interpret=False):
    """Entries 1 and 2: `rows [M, K]` against `table [G, K, N]` (`[G, N, K]`
    with `transposed`) -> `[M, N]`."""
    from jax.experimental.pallas import tpu as pltpu

    (M, K), (_, a, b) = rows.shape, table.shape
    N = a if transposed else b
    *lists, count = _visits(group_sizes, M, tm, empty_groups=False)

    def by_tile(width):
        return pl.BlockSpec((tm, width), lambda i, offsets, group, tile:
                            (tile[i], 0))

    return pl.pallas_call(
        functools.partial(_rows_table_kernel, tm=tm, transposed=transposed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(count,),
            in_specs=[by_tile(K), pl.BlockSpec(
                (None, a, b), lambda i, offsets, group, tile:
                (group[i], 0, 0))],
            out_specs=by_tile(N)),
        out_shape=jax.ShapeDtypeStruct((M, N), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=f"grouped_matmul_rows_table{'_t' if transposed else ''}",
    )(*lists, rows, table)


@functools.partial(jax.jit, static_argnames=("tm", "ta", "tb", "interpret"))
def contracted_pallas(rows, ct, group_sizes, *, tm, ta, tb, interpret=False):
    """Entry 3: `rows [M, A]`, `ct [M, B]` -> `[G, A, B]`, an `[ta, tb]` part
    of every group's matrix at a time: the visits are the grid's last axis."""
    from jax.experimental.pallas import tpu as pltpu

    (M, A), B, G = rows.shape, ct.shape[1], group_sizes.shape[0]
    *lists, count = _visits(group_sizes, M, tm, empty_groups=True)
    return pl.pallas_call(
        functools.partial(_contracted_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(A // ta, B // tb, count),
            in_specs=[
                pl.BlockSpec((tm, ta), lambda a, b, i, offsets, group, tile:
                             (tile[i], a)),
                pl.BlockSpec((tm, tb), lambda a, b, i, offsets, group, tile:
                             (tile[i], b))],
            out_specs=pl.BlockSpec(
                (None, ta, tb), lambda a, b, i, offsets, group, tile:
                (group[i], a, b)),
            scratch_shapes=[pltpu.VMEM((ta, tb), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((G, A, B), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name="grouped_matmul_contracted",
    )(*lists, rows, ct)


# ------------------------------------------------------------- the registry
def _divisors(n):
    """`n` and its parts on the 128-lane grid, largest first."""
    return [n // k for k in range(1, n // 128 + 1)
            if n % k == 0 and (n // k) % 128 == 0]


def tiling(entry, M, K, N, itemsize):
    """The tiles for a signature, or None where none serves: `(tm,)` for
    entries 1 and 2, `(tm, ta, tb)` for entry 3 (K, N its A, B). A row tile
    divides M and leaves `_MIN_ROW_TILES` tiles; a step's blocks, each
    twice (the pipeline's two buffers) with the float32 product beside
    them, stay under `_VMEM_BUDGET`. Entry 3 takes the fewest parts of
    `[A, B]` that fit, then the tallest row tile."""
    def row_tiles(tallest):
        return [tm for tm in (512, 256, 128) if tm <= tallest
                and M % tm == 0 and M >= _MIN_ROW_TILES * tm]

    if entry != "contracted":
        # 256 rows: within 3% of the best tile at every shape probed
        for tm in row_tiles(256):
            if (2 * tm * K + 2 * K * N + 2 * tm * N) * itemsize + (
                    tm * N * 4) <= _VMEM_BUDGET:
                return (tm,)
        return None
    parts = sorted(((ta, tb) for ta in _divisors(K) for tb in _divisors(N)),
                   key=lambda t: (-t[0] * t[1], t[0] + t[1]))
    for ta, tb in parts:
        for tm in row_tiles(512):
            if (3 * tm * (ta + tb) + 2 * ta * tb) * itemsize + (
                    ta * tb * 4) <= _VMEM_BUDGET:
                return tm, ta, tb
    return None


def _pallas_available(backend, shapes, dtypes, meta=(), forced=False):
    """`shapes` is `(M, K, N, G)` (entry 3: K and N are the table's A and
    B), `dtypes` the two operands', `meta` holds the `entry`."""
    if backend != "tpu" and not forced:
        return False, ("auto off-TPU keeps jax.lax.ragged_dot (interpret "
                       "mode is for the forced parity tests)")
    if dtypes and (len(set(dtypes)) > 1
                   or dtypes[0] not in ("bfloat16", "float32")):
        return False, (f"dtypes {dtypes}: the kernel takes both operands in "
                       "bfloat16 or both in float32")
    if shapes:
        M, K, N, G = shapes
        if K % 128 or N % 128:
            return False, (f"K={K}, N={N}: the kernel holds whole [K, N] "
                           "matrices and wants both on the 128-lane grid")
        if tiling(dict(meta).get("entry", "rows_table"), M, K, N,
                  2 if dtypes[:1] == ("bfloat16",) else 4) is None:
            return False, (f"M={M}, K={K}, N={N}: under {_MIN_ROW_TILES} row "
                           f"tiles of 128, M off that grid, or a step's "
                           f"blocks outgrow the {_VMEM_BUDGET >> 20} MiB of "
                           "VMEM a kernel has")
    if backend == "tpu":
        return True, ("TPU grouped-product kernel (a visit list over row "
                      "tiles and groups from the traced group sizes, whole "
                      "[K, N] matrices in VMEM)")
    return True, "interpret mode off-TPU (float-close parity tests only)"


def _xla_available(backend, shapes, dtypes, meta=(), forced=False):
    return True, "jax.lax.ragged_dot / ragged_dot_general (XLA's lowering)"


_registry.register("grouped_matmul", [
    _registry.KernelImpl("pallas", _pallas_available),
    _registry.KernelImpl("xla", _xla_available),
])


def _resolve(entry, M, K, N, G, rows_dtype, other_dtype):
    """-> the tiles where the Pallas body resolved, None for XLA's."""
    res = _registry.resolve(
        "grouped_matmul", shapes=(int(M), int(K), int(N), int(G)),
        dtypes=(str(rows_dtype), str(other_dtype)), meta=(("entry", entry),))
    if res.impl != "pallas":
        return None
    return tiling(entry, M, K, N, jnp.dtype(rows_dtype).itemsize)


def rows_table(rows, table, group_sizes):
    """Entry 1: `rows [M, K]` by group against `table [G, K, N]`."""
    G, K, N = table.shape
    tiles = _resolve("rows_table", rows.shape[0], K, N, G, rows.dtype,
                     table.dtype)
    if tiles is not None:
        return rows_table_pallas(rows, table, group_sizes, tm=tiles[0],
                                 interpret=_registry.interpret_mode())
    return jax.lax.ragged_dot(rows, table, group_sizes)


class Transposed(NamedTuple):
    """A table `[G, N, K]` as `rows_table_t` takes it: as stored with the
    Pallas body's row tile, or the `[G, K, N]` copy XLA's lowering wants."""

    table: jax.Array
    tm: int | None


def transposed(table, rows: int, dtype):
    """`table [G, N, K]` for `rows_table_t` against `[rows, K]` of `dtype`.
    The Pallas body contracts a matrix's last axis as it lies; XLA's
    `ragged_dot` contracts the middle one, so its candidate gets the
    transposed copy, made here: at the caller's place of choice, once."""
    G, N, K = table.shape
    tiles = _resolve("rows_table_t", rows, K, N, G, dtype, table.dtype)
    if tiles is None:
        return Transposed(jnp.swapaxes(table, 1, 2), None)
    return Transposed(table, tiles[0])


def rows_table_t(rows, table_t: Transposed, group_sizes):
    """Entry 2: `rows [M, K]` by group against the transposes of a table
    `[G, N, K]` that went through `transposed`."""
    if table_t.tm is not None:
        return rows_table_pallas(rows, table_t.table, group_sizes,
                                 tm=table_t.tm, transposed=True,
                                 interpret=_registry.interpret_mode())
    return jax.lax.ragged_dot(rows, table_t.table, group_sizes)


def contracted(rows, ct, group_sizes):
    """Entry 3: `rows [M, A]`, `ct [M, B]` -> `[G, A, B]`, each group's rows
    contracted: what `jax.lax.ragged_dot`'s own transpose makes of a table's
    cotangent."""
    tiles = _resolve("contracted", *rows.shape, ct.shape[1],
                     group_sizes.shape[0], rows.dtype, ct.dtype)
    if tiles is not None:
        tm, ta, tb = tiles
        return contracted_pallas(rows, ct, group_sizes, tm=tm, ta=ta, tb=tb,
                                 interpret=_registry.interpret_mode())
    return jax.lax.ragged_dot_general(rows, ct, group_sizes, GROUPS_CONTRACTED)
