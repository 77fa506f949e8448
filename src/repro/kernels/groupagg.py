"""Masked group-by aggregation kernel (DESIGN §4: §2.4 per-partition A_{g,i}).

The executor's hot loop: for each partition, segment-sum V aggregate
component rows (component 0 = the passing-row indicator) into G group
buckets under a predicate mask.  GPU implementations scatter-add; the TPU
adaptation builds a row-tile one-hot (G × T) group matrix and contracts it
against the masked values on the MXU:

    out[v, g] = Σ_t  values[v, t] · mask[t] · 1[codes[t] = g]
              = (values ⊙ mask) @ onehot(codes)ᵀ

Grid: (partitions, group_tiles, row_tiles) — row tiles accumulate into the
same (V, bg) output block (sequential revisiting).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import (
    LANE, SUBLANE, contract_rows, interpret, pick_block, round_up,
)


def _kernel(vals_ref, codes_ref, o_ref, *, bg: int):
    v = vals_ref[0].astype(jnp.float32)  # (V, bt) — masked values
    c = codes_ref[0]  # (1, bt) int32, -1 = padding/masked-out

    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    bins = pl.program_id(1) * bg + jax.lax.broadcasted_iota(jnp.int32, (bg, 1), 0)
    o_ref[0] += contract_rows(v, c == bins)  # (bg, bt) one-hot


@functools.partial(jax.jit, static_argnames=("num_groups", "block_rows", "block_groups"))
def group_aggregate(
    values: jax.Array,  # (P, V, R) aggregate components per row
    mask: jax.Array,  # (P, R) bool/0-1 predicate mask
    codes: jax.Array,  # (P, R) int32 group codes in [0, num_groups)
    num_groups: int,
    block_rows: int = 1024,
    block_groups: int = 512,
) -> jax.Array:
    """→ (P, V, num_groups) masked per-partition segment sums."""
    p, v, r = values.shape
    bt = pick_block(r, block_rows, LANE)
    rp = round_up(r, bt)
    vp = round_up(v, SUBLANE)
    bg = pick_block(num_groups, block_groups, LANE)
    gp = round_up(num_groups, bg)
    masked = values * mask[:, None, :].astype(values.dtype)
    vals = jnp.pad(masked, ((0, 0), (0, vp - v), (0, rp - r)))
    # fold the mask into the codes: masked-out rows get code -1 (no bucket)
    mcodes = jnp.where(mask.astype(bool), codes.astype(jnp.int32), -1)
    mcodes = jnp.pad(mcodes, ((0, 0), (0, rp - r)), constant_values=-1)[:, None, :]
    out = pl.pallas_call(
        functools.partial(_kernel, bg=bg),
        grid=(p, gp // bg, rp // bt),
        in_specs=[
            pl.BlockSpec((1, vp, bt), lambda i, j, l: (i, 0, l)),
            pl.BlockSpec((1, 1, bt), lambda i, j, l: (i, 0, l)),
        ],
        out_specs=pl.BlockSpec((1, vp, bg), lambda i, j, l: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((p, vp, gp), jnp.float32),
        interpret=interpret(),
    )(vals, mcodes)
    return out[:, :v, :num_groups]
