"""Histogram / bincount kernels (DESIGN §4: §3.1 equi-depth + §3.2 counts).

GPU implementations scatter into bins (atomics); the TPU adaptation
reformulates binning as *compare-against-edges + matmul popcount*: each
row tile produces a one-hot (bins × rows) matrix — bins on the sublanes,
rows on the lanes, the layout the row tile already arrives in — that the
MXU reduces with a ones-matrix contraction over the row (lane) axis into a
lane-dense (8, bins) block.  The one-hot and the ones are both 0/1, so the
contraction is exact at any matmul precision.  Two entry points share the
pattern:

* `histogram_range(x, edges)` — numeric values against per-partition
  equi-depth bucket edges (B buckets = B+1 edges; final bucket inclusive).
* `bincount(codes, card)` — exact categorical frequencies (the lossy-
  counting replacement, DESIGN §3).

Each partition's row vector is viewed as a (P, 1, R) array so a row tile
is a (1, bt) block; bins are padded to the lane width (and tiled along a
grid axis for wide bincounts); row tiles accumulate over the sequential
grid axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import LANE, SUBLANE, interpret, pick_block, round_up

_BLOCK_BINS = 512  # bincount's bin tile: wide cardinalities take several


def _popcount(onehot: jax.Array) -> jax.Array:
    """(bins, bt) 0/1 → (8, bins) row sums, every row identical."""
    ones = jnp.ones((SUBLANE, onehot.shape[1]), jnp.float32)
    return jax.lax.dot_general(
        ones, onehot.astype(jnp.float32), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _range_kernel(x_ref, lo_ref, hi_ref, o_ref, *, nb: int):
    x = x_ref[0].astype(jnp.float32)  # (1, bt)
    lo = lo_ref[0]  # (bpad, 1)
    hi = hi_ref[0]
    last = jax.lax.broadcasted_iota(jnp.int32, lo.shape, 0) == nb - 1

    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    onehot = (x >= lo) & ((x < hi) | (last & (x <= hi)))  # (bpad, bt)
    o_ref[0] += _popcount(onehot)


@functools.partial(jax.jit, static_argnames=("block_rows",))
def histogram_range(x: jax.Array, edges: jax.Array, block_rows: int = 1024) -> jax.Array:
    """(P, R) values + (P, B+1) edges → (P, B) bucket counts.

    Values outside [edges[0], edges[-1]] fall into no bucket (matching the
    reference); the final bucket includes its upper edge.
    """
    p, r = x.shape
    nb = edges.shape[1] - 1
    bt = pick_block(r, block_rows, LANE)
    rp = round_up(r, bt)
    bpad = round_up(nb, LANE)
    inf = jnp.float32(jnp.inf)
    xp = jnp.pad(x, ((0, 0), (0, rp - r)), constant_values=jnp.nan)[:, None, :]
    lo = jnp.pad(edges[:, :-1].astype(jnp.float32), ((0, 0), (0, bpad - nb)),
                 constant_values=inf)[:, :, None]
    hi = jnp.pad(edges[:, 1:].astype(jnp.float32), ((0, 0), (0, bpad - nb)),
                 constant_values=-inf)[:, :, None]
    out = pl.pallas_call(
        functools.partial(_range_kernel, nb=nb),
        grid=(p, rp // bt),
        in_specs=[
            pl.BlockSpec((1, 1, bt), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, bpad, 1), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, bpad, 1), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, SUBLANE, bpad), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((p, SUBLANE, bpad), jnp.float32),
        interpret=interpret(),
    )(xp, lo, hi)
    return out[:, 0, :nb]


def _bincount_kernel(codes_ref, o_ref, *, bb: int):
    c = codes_ref[0]  # (1, bt) int32; -1 = padding

    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    bins = pl.program_id(1) * bb + jax.lax.broadcasted_iota(jnp.int32, (bb, 1), 0)
    o_ref[0] += _popcount(c == bins)  # (bb, bt) one-hot


@functools.partial(jax.jit, static_argnames=("card", "block_rows"))
def bincount(codes: jax.Array, card: int, block_rows: int = 1024) -> jax.Array:
    """(P, R) int codes in [0, card) → (P, card) exact counts."""
    p, r = codes.shape
    bt = pick_block(r, block_rows, LANE)
    rp = round_up(r, bt)
    bb = pick_block(card, _BLOCK_BINS, LANE)
    bpad = round_up(card, bb)
    cp = jnp.pad(codes.astype(jnp.int32), ((0, 0), (0, rp - r)),
                 constant_values=-1)[:, None, :]
    out = pl.pallas_call(
        functools.partial(_bincount_kernel, bb=bb),
        grid=(p, bpad // bb, rp // bt),
        in_specs=[pl.BlockSpec((1, 1, bt), lambda i, j, l: (i, 0, l))],
        out_specs=pl.BlockSpec((1, SUBLANE, bb), lambda i, j, l: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((p, SUBLANE, bpad), jnp.float32),
        interpret=interpret(),
    )(cp)
    return out[:, 0, :card]
