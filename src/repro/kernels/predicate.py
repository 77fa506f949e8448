"""Fused predicate-evaluation kernel (DESIGN §4: §3.2 executor filter).

Evaluates an AND-of-OR-groups predicate over C single-column clauses in one
VMEM pass.  The host wrapper gathers the referenced columns into a (C, R)
stack (columns used by several clauses are duplicated — C ≤ 10 in the
paper's clustering scope) and canonicalizes every clause to a half-open
interval test  lo ≤ x < hi  (equality on coded categoricals becomes
[v, v+1); negation flips to the complement pair handled by two clauses at
IR level).  In-kernel, clause results are OR-combined within groups by a
max over member clauses (an unrolled loop over the C clause rows against
the (G, C) clause→group map) and AND-combined across groups by a min over
the group rows — branch-free, VPU-only, one pass, rows on the lanes.

Outputs both the row mask and the per-partition passing count (the
selectivity ground truth used for picker training labels).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import LANE, interpret, pick_block, round_up


def tile_mask(x, lo, hi, gmap_t):
    """(C, bt) columns, (C, 1) bounds, (G, C) group map → (1, bt) 0/1 mask.

    OR within groups is a max over member clauses (exact for overlapping
    maps too); AND across groups is a min over the group rows."""
    cf = ((x >= lo) & (x < hi)).astype(jnp.float32)  # (C, bt)
    grouped = jnp.zeros((gmap_t.shape[0], x.shape[1]), jnp.float32)
    for k in range(x.shape[0]):
        grouped = jnp.maximum(grouped, gmap_t[:, k : k + 1] * cf[k : k + 1, :])
    return jnp.min(grouped, axis=0, keepdims=True)


def _kernel(x_ref, lo_ref, hi_ref, gmap_ref, o_ref):
    o_ref[0] = tile_mask(
        x_ref[0].astype(jnp.float32), lo_ref[0], hi_ref[0], gmap_ref[0]
    )


def canonical_operands(cols, lo, hi, group_map, rp):
    """Kernel operand layouts shared with `fused.fused_eval`: row-padded
    columns (NaN pad fails every interval test ⇒ mask 0), (B, C, 1) bounds
    and the (B, G, C) transposed clause→group map."""
    b, c, r = cols.shape
    xp = jnp.pad(cols.astype(jnp.float32), ((0, 0), (0, 0), (0, rp - r)),
                 constant_values=jnp.nan)
    lo = lo.astype(jnp.float32)[:, :, None]
    hi = hi.astype(jnp.float32)[:, :, None]
    gmap_t = jnp.swapaxes(group_map.astype(jnp.float32), 1, 2)
    return xp, lo, hi, gmap_t


@functools.partial(jax.jit, static_argnames=("num_groups", "block_rows"))
def predicate_eval(
    cols: jax.Array,  # (P, C, R) gathered clause columns
    lo: jax.Array,  # (P, C) or (C,) inclusive lower bounds
    hi: jax.Array,  # (P, C) or (C,) exclusive upper bounds
    group_map: jax.Array,  # (C, G) or (P, C, G) one-hot clause→OR-group map
    num_groups: int,
    block_rows: int = 1024,
) -> tuple[jax.Array, jax.Array]:
    """→ (mask (P, R) float 0/1, count (P,)) for the AND-of-ORs predicate.

    A 3-D `group_map` carries one clause→group map per partition row — the
    stacked-query driver packs Q queries along the partition axis, and each
    query brings its own OR-group structure.
    """
    p, c, r = cols.shape
    g = group_map.shape[-1]
    bt = pick_block(r, block_rows, LANE)
    rp = round_up(r, bt)
    if lo.ndim == 1:
        lo = jnp.broadcast_to(lo[None], (p, c))
        hi = jnp.broadcast_to(hi[None], (p, c))
    if group_map.ndim == 2:
        group_map = jnp.broadcast_to(group_map[None], (p, c, num_groups))
    xp, lo, hi, gmap_t = canonical_operands(cols, lo, hi, group_map, rp)
    mask = pl.pallas_call(
        _kernel,
        grid=(p, rp // bt),
        in_specs=[
            pl.BlockSpec((1, c, bt), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, c, 1), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, c, 1), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, g, c), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bt), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((p, 1, rp), jnp.float32),
        interpret=interpret(),
    )(xp, lo, hi, gmap_t)
    mask = mask[:, 0, :r]
    return mask, jnp.sum(mask, axis=1)
