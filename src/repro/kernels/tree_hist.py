"""GBDT gradient/hessian histogram kernel (DESIGN §4: §4.3 funnel training).

The level-wise tree learner's hot loop scatters every (row, sampled
feature) pair's gradient and hessian into a ``(nodes, features, bins)``
histogram — on GPU an atomic scatter-add.  The TPU adaptation follows the
`groupagg` pattern: per sampled feature column, a row tile builds a one-hot
``(node·bin-segments × rows)`` matrix that the MXU contracts against a
``(g; h)`` two-row stack, so one launch produces both histograms for every
node of the current level:

    out[c, {g,h}, s] = Σ_t  gh[{g,h}, t] · 1[node[t]·B + code[t, c] = s]

Rows with ``node < 0`` (pad rows / masked-out subsample slots) hit no
segment.  The per-feature ``(2, nodes·bins)`` panels are placed into the
full feature axis outside the kernel (the sampled-column gather is cheap;
unsampled features keep all-zero histograms, which the split search already
treats as dead — the same convention the host fit uses).

Grid: (columns, segment_tiles, row_tiles) — row tiles accumulate into the
same (8, bs) output block (sequential revisiting), exactly like `groupagg`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import (
    LANE, SUBLANE, contract_rows, interpret, pick_block, round_up,
)


def _kernel(codes_ref, node_ref, gh_ref, o_ref, *, bs: int, num_bins: int):
    c = codes_ref[0]  # (1, bt) int32 bin codes for this feature column
    nd = node_ref[...]  # (1, bt) int32 level-node index; -1 = dropped row
    gh = gh_ref[...]  # (8, bt) f32; row 0 = g, row 1 = h, rest zero

    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    seg = jnp.where(nd >= 0, nd * num_bins + c, -1)  # (1, bt) node·B + bin
    bins = pl.program_id(1) * bs + jax.lax.broadcasted_iota(jnp.int32, (bs, 1), 0)
    o_ref[0] += contract_rows(gh, seg == bins)  # (bs, bt) one-hot


@functools.partial(
    jax.jit,
    static_argnames=("num_nodes", "num_feats", "num_bins", "block_rows", "block_segs"),
)
def tree_hist(
    codes: jax.Array,  # (R, C) int32 bin codes of the C sampled feature columns
    feat_ids: jax.Array,  # (C,) int32 global feature id per sampled column
    node: jax.Array,  # (R,) int32 level-node index in [0, num_nodes); -1 drops
    g: jax.Array,  # (R,) f32 gradients
    h: jax.Array,  # (R,) f32 hessians
    num_nodes: int,
    num_feats: int,
    num_bins: int = 256,
    block_rows: int = 1024,
    block_segs: int = 512,
) -> jax.Array:
    """→ (2, num_nodes, num_feats, num_bins) G/H histograms (f32)."""
    r, c = codes.shape
    s = num_nodes * num_bins
    bt = pick_block(r, block_rows, LANE)
    rp = round_up(r, bt)
    bs = pick_block(s, block_segs, LANE)
    sp = round_up(s, bs)
    codes_t = jnp.pad(codes.astype(jnp.int32).T, ((0, 0), (0, rp - r)))[:, None, :]
    node_p = jnp.pad(node.astype(jnp.int32)[None], ((0, 0), (0, rp - r)), constant_values=-1)
    gh = jnp.zeros((SUBLANE, rp), jnp.float32)
    gh = gh.at[0, :r].set(g.astype(jnp.float32)).at[1, :r].set(h.astype(jnp.float32))
    out = pl.pallas_call(
        functools.partial(_kernel, bs=bs, num_bins=num_bins),
        grid=(c, sp // bs, rp // bt),
        in_specs=[
            pl.BlockSpec((1, 1, bt), lambda i, j, l: (i, 0, l)),
            pl.BlockSpec((1, bt), lambda i, j, l: (0, l)),
            pl.BlockSpec((SUBLANE, bt), lambda i, j, l: (0, l)),
        ],
        out_specs=pl.BlockSpec((1, SUBLANE, bs), lambda i, j, l: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((c, SUBLANE, sp), jnp.float32),
        interpret=interpret(),
    )(codes_t, node_p, gh)
    # (C, 2, nodes, bins) panels → full feature axis (unsampled stay zero)
    panels = out[:, :2, :s].reshape(c, 2, num_nodes, num_bins)
    full = jnp.zeros((2, num_nodes, num_feats, num_bins), jnp.float32)
    return full.at[:, :, feat_ids].set(panels.transpose(1, 2, 0, 3))
