"""Fused moments sketch kernel (DESIGN §4: the §3.1 ingest pass).

One streaming HBM→VMEM pass per partition computes ALL measure statistics
the paper stores per column (§3.1 Table 2): min, max, Σx, Σx², and the
log-transform variants min/max/Σlog/Σlog² — eight accumulators in one read
instead of the four separate passes a sketch-per-pass implementation would
make.  The kernel is memory-bound by construction (8 flops/elem vs 4 bytes
read), so fusing the passes is the whole optimization.

The power sums are taken about a per-partition shift ``s`` (the
partition's first value, 0 if non-finite): Σ(x−s) and Σ(x−s)².  Raw f32
Σx² cannot carry a variance that is small next to the mean² — a date
column's partition on a date-sorted layout spans a few days around
~1,000, so Σx² ≈ 1.6e10 at 16,384 rows, whose f32 ulp swamps the
variance — while the shifted sums keep it.  The shift and the row count
ride along in the output so the host reconstructs mean / meansq / std in
float64 (`core.ingest.measures_from_moments`) and row-chunk partials
re-center exactly (`core.ingest.merge_moments`).

Grid: (partition_tiles, row_tiles).  Each step reads an (8, bt) tile —
eight partitions on the sublanes, rows on the lanes — and reduces every
statistic along the lanes, so each partition's fold stays row-local.  The
eight statistics sit in lanes 0..7 of a lane-dense (8, 128) output block
that the row-tile axis accumulates into (sequential revisiting: the output
block index is independent of the reduced axis), combined per lane by
min, max or add.

Row validity comes from the lane index (rows past R are padding), so no
mask array is streamed; log statistics use max(x, tiny) exactly like the
host reference so allclose tests are exact-modulo-float.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import LANE, SUBLANE, interpret, pick_block, round_up

# min, max, Σ(x−s), Σ(x−s)², logmin, logmax, logsum, logsumsq, shift s, rows
NSTATS = 10
SHIFT = 8
_TINY = 1e-30
_MIN_LANES = (0, 4)
_MAX_LANES = (1, 5)


def _lanes_in(lane, which):
    return functools.reduce(jnp.logical_or, [lane == k for k in which])


def _kernel(x_ref, o_ref, *, rows: int, bt: int):
    j = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)  # (8, bt)
    lane = jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 1)
    is_min = _lanes_in(lane, _MIN_LANES)
    is_max = _lanes_in(lane, _MAX_LANES)

    @pl.when(j == 0)
    def _init():
        x0 = x[:, 0:1]
        shift = jnp.where(jnp.isfinite(x0), x0, 0.0)
        o_ref[...] = jnp.where(
            is_min, jnp.inf,
            jnp.where(is_max, -jnp.inf, jnp.where(lane == SHIFT, shift, 0.0)),
        )

    acc = o_ref[...]
    col = j * bt + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    v = col < rows
    lx = jnp.log(jnp.maximum(x, _TINY))
    d = jnp.where(v, x - acc[:, SHIFT : SHIFT + 1], 0.0)
    lm = jnp.where(v, lx, 0.0)
    stats = (
        jnp.min(jnp.where(v, x, jnp.inf), axis=1, keepdims=True),
        jnp.max(jnp.where(v, x, -jnp.inf), axis=1, keepdims=True),
        jnp.sum(d, axis=1, keepdims=True),
        jnp.sum(d * d, axis=1, keepdims=True),
        jnp.min(jnp.where(v, lx, jnp.inf), axis=1, keepdims=True),
        jnp.max(jnp.where(v, lx, -jnp.inf), axis=1, keepdims=True),
        jnp.sum(lm, axis=1, keepdims=True),
        jnp.sum(lm * lx, axis=1, keepdims=True),
    )  # each (8, 1); the shift lane adds 0, the rows lane counts
    tile = jnp.where(lane == NSTATS - 1, jnp.sum(v.astype(jnp.float32), axis=1,
                                                 keepdims=True), 0.0)
    for k, s in enumerate(stats):
        tile = jnp.where(lane == k, s, tile)
    o_ref[...] = jnp.where(
        is_min, jnp.minimum(acc, tile),
        jnp.where(is_max, jnp.maximum(acc, tile), acc + tile),
    )


@functools.partial(jax.jit, static_argnames=("block_rows",))
def moments(x: jax.Array, block_rows: int = 2048) -> jax.Array:
    """(P, R) values → (P, NSTATS) fused measure statistics."""
    p, r = x.shape
    bt = pick_block(r, block_rows, LANE)
    rp = round_up(r, bt)
    pp = round_up(p, SUBLANE)
    xp = jnp.pad(x, ((0, pp - p), (0, rp - r)))
    out = pl.pallas_call(
        functools.partial(_kernel, rows=r, bt=bt),
        grid=(pp // SUBLANE, rp // bt),
        in_specs=[pl.BlockSpec((SUBLANE, bt), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((SUBLANE, LANE), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((pp, LANE), jnp.float32),
        interpret=interpret(),
    )(xp)
    return out[:p, :NSTATS]
