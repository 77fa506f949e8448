"""Fused predicate-eval + group-aggregate kernel (one launch, no mask HBM).

`predicate.py` and `groupagg.py` run the executor hot loop as two
launches with a (B, R) row-mask tensor round-tripping through HBM between
them.  This kernel fuses both: each row tile evaluates the AND-of-ORs
interval predicate in VMEM (`predicate.tile_mask`), folds the resulting
mask straight into the group codes, and contracts the tile one-hot
against the aggregate components on the MXU (the `groupagg.py` trick) —
the mask never exists outside the tile.

Grid: (batch, group_tiles, row_tiles); row tiles accumulate into the same
(V, bg) output block (sequential revisiting).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import (
    LANE, SUBLANE, contract_rows, interpret, pick_block, round_up,
)
from repro.kernels.predicate import canonical_operands, tile_mask


def _kernel(x_ref, lo_ref, hi_ref, gmap_ref, vals_ref, codes_ref, o_ref, *, bg: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    mask = tile_mask(x_ref[0], lo_ref[0], hi_ref[0], gmap_ref[0])  # (1, bt)
    c = codes_ref[0]  # (1, bt) int32, -1 = padding
    # aggregate: fold the mask into the codes, contract the tile one-hot
    mcodes = jnp.where((mask > 0.5) & (c >= 0), c, -1)
    bins = pl.program_id(1) * bg + jax.lax.broadcasted_iota(jnp.int32, (bg, 1), 0)
    o_ref[0] += contract_rows(vals_ref[0], mcodes == bins)  # (bg, bt) one-hot


@functools.partial(
    jax.jit, static_argnames=("num_groups", "block_rows", "block_groups")
)
def fused_eval(
    cols: jax.Array,  # (B, C, R) gathered clause columns
    lo: jax.Array,  # (B, C) inclusive lower bounds
    hi: jax.Array,  # (B, C) exclusive upper bounds
    group_map: jax.Array,  # (B, C, G) one-hot clause→OR-group map
    values: jax.Array,  # (B, V, R) aggregate components per row
    codes: jax.Array,  # (B, R) int32 group-by codes in [0, num_groups)
    num_groups: int,
    block_rows: int = 1024,
    block_groups: int = 512,
) -> jax.Array:
    """→ (B, V, num_groups) masked per-row-batch segment sums."""
    b, c, r = cols.shape
    g = group_map.shape[2]  # OR-group count (independent of num_groups)
    v = values.shape[1]
    bt = pick_block(r, block_rows, LANE)
    rp = round_up(r, bt)
    vp = round_up(v, SUBLANE)
    bg = pick_block(num_groups, block_groups, LANE)
    gp = round_up(num_groups, bg)
    xp, lo, hi, gmap_t = canonical_operands(cols, lo, hi, group_map, rp)
    vals = jnp.pad(values.astype(jnp.float32), ((0, 0), (0, vp - v), (0, rp - r)))
    cp = jnp.pad(codes.astype(jnp.int32), ((0, 0), (0, rp - r)),
                 constant_values=-1)[:, None, :]
    out = pl.pallas_call(
        functools.partial(_kernel, bg=bg),
        grid=(b, gp // bg, rp // bt),
        in_specs=[
            pl.BlockSpec((1, c, bt), lambda i, j, l: (i, 0, l)),
            pl.BlockSpec((1, c, 1), lambda i, j, l: (i, 0, 0)),
            pl.BlockSpec((1, c, 1), lambda i, j, l: (i, 0, 0)),
            pl.BlockSpec((1, g, c), lambda i, j, l: (i, 0, 0)),
            pl.BlockSpec((1, vp, bt), lambda i, j, l: (i, 0, l)),
            pl.BlockSpec((1, 1, bt), lambda i, j, l: (i, 0, l)),
        ],
        out_specs=pl.BlockSpec((1, vp, bg), lambda i, j, l: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((b, vp, gp), jnp.float32),
        interpret=interpret(),
    )(xp, lo, hi, gmap_t, vals, cp)
    return out[:, :v, :num_groups]
