"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

_TINY = 1e-30


def moments_ref(x: jax.Array) -> jax.Array:
    """(P, R) → (P, 10): min, max, Σ(x−s), Σ(x−s)², logmin, logmax,
    logsum, logsumsq, s, rows — s is each partition's first value (0 if
    non-finite), as in `kernels/moments.py`."""
    x = x.astype(jnp.float32)
    lx = jnp.log(jnp.maximum(x, _TINY))
    shift = jnp.where(jnp.isfinite(x[:, 0]), x[:, 0], 0.0)
    d = x - shift[:, None]
    return jnp.stack(
        [
            jnp.min(x, axis=1),
            jnp.max(x, axis=1),
            jnp.sum(d, axis=1),
            jnp.sum(d * d, axis=1),
            jnp.min(lx, axis=1),
            jnp.max(lx, axis=1),
            jnp.sum(lx, axis=1),
            jnp.sum(lx * lx, axis=1),
            shift,
            jnp.full(x.shape[:1], x.shape[1], jnp.float32),
        ],
        axis=1,
    )


def histogram_range_ref(x: jax.Array, edges: jax.Array) -> jax.Array:
    x = x.astype(jnp.float32)
    lo = edges[:, :-1].astype(jnp.float32)  # (P, B)
    hi = edges[:, 1:].astype(jnp.float32)
    nb = lo.shape[1]
    xt = x[:, :, None]
    inb = (xt >= lo[:, None, :]) & (xt < hi[:, None, :])
    last = (xt >= lo[:, None, :]) & (xt <= hi[:, None, :])
    sel = jnp.concatenate([inb[..., : nb - 1], last[..., nb - 1 :]], axis=-1)
    return jnp.sum(sel.astype(jnp.float32), axis=1)


def bincount_ref(codes: jax.Array, card: int) -> jax.Array:
    onehot = jax.nn.one_hot(codes, card, dtype=jnp.float32)
    return jnp.sum(onehot, axis=1)


def pdist_sq_ref(x: jax.Array, centers: jax.Array) -> jax.Array:
    x = x.astype(jnp.float32)
    c = centers.astype(jnp.float32)
    d = x[:, None, :] - c[None, :, :]
    return jnp.sum(d * d, axis=-1)


def blocked_onehot_aggregate(
    values: jax.Array,  # (P, V, R) f32 aggregate components (already masked OK)
    codes: jax.Array,  # (P, R) int32 group codes; -1 = dropped row
    num_groups: int,
    block_rows: int = 512,
) -> jax.Array:
    """Scatter-free segment sum: scan fixed row tiles, contract a
    (tile × num_groups) one-hot per tile on the matmul unit.

    Memory stays O(P · block · num_groups) instead of the all-at-once
    (P, R, num_groups) one-hot tensor, and XLA parallelizes the batched
    dot on CPU where `segment_sum`'s scatter serializes.  The tile size
    depends only on R (never on P or the query batch), so per-partition
    sums are bitwise identical between single-device and sharded runs.
    """
    p, v, r = values.shape
    bt = min(block_rows, r)
    nb = -(-r // bt)
    rp = nb * bt
    vals = jnp.pad(values.astype(jnp.float32), ((0, 0), (0, 0), (0, rp - r)))
    mcodes = jnp.pad(codes.astype(jnp.int32), ((0, 0), (0, rp - r)),
                     constant_values=-1)
    # (nb, P, V, bt) / (nb, P, bt) row tiles for the scan
    vals_t = jnp.moveaxis(vals.reshape(p, v, nb, bt), 2, 0)
    codes_t = jnp.moveaxis(mcodes.reshape(p, nb, bt), 1, 0)
    bins = jnp.arange(num_groups, dtype=jnp.int32)

    def step(acc, tile):
        vt, ct = tile
        onehot = (ct[:, :, None] == bins).astype(jnp.float32)  # (P, bt, G)
        upd = jax.lax.dot_general(
            vt, onehot, (((2,), (1,)), ((0,), (0,))),
            precision=jax.lax.Precision.HIGHEST,  # TPU default rounds to bf16
            preferred_element_type=jnp.float32,
        )
        return acc + upd, None

    acc0 = jnp.zeros((p, v, num_groups), jnp.float32)
    out, _ = jax.lax.scan(step, acc0, (vals_t, codes_t))
    return out


def group_aggregate_ref(
    values: jax.Array, mask: jax.Array, codes: jax.Array, num_groups: int
) -> jax.Array:
    """(P, V, R) masked segment sums via the blocked one-hot matmul."""
    masked = values.astype(jnp.float32) * mask[:, None, :].astype(jnp.float32)
    mcodes = jnp.where(mask.astype(bool), codes.astype(jnp.int32), -1)
    return blocked_onehot_aggregate(masked, mcodes, num_groups)


def fused_eval_ref(
    cols: jax.Array,  # (B, C, R) gathered clause columns
    lo: jax.Array,  # (B, C) inclusive lower bounds
    hi: jax.Array,  # (B, C) exclusive upper bounds
    group_map: jax.Array,  # (B, C, G) one-hot clause→OR-group map
    values: jax.Array,  # (B, V, R) aggregate components
    codes: jax.Array,  # (B, R) int32 group-by codes
    num_groups: int,
) -> jax.Array:
    """Fused predicate-eval + group-aggregate: → (B, V, num_groups).

    The row mask only ever exists tile-by-tile inside the blocked
    aggregation — fusing the compare into the code fold means XLA never
    materializes a separate (B, R) mask tensor between two launches.
    """
    x = cols.astype(jnp.float32)
    clause = ((x >= lo[:, :, None]) & (x < hi[:, :, None])).astype(jnp.float32)
    grouped = jnp.einsum("bcr,bcg->bgr", clause, group_map.astype(jnp.float32))
    mask = jnp.all(grouped > 0.5, axis=1)  # (B, R) AND over OR-groups
    masked = values.astype(jnp.float32) * mask[:, None, :].astype(jnp.float32)
    mcodes = jnp.where(mask, codes.astype(jnp.int32), -1)
    return blocked_onehot_aggregate(masked, mcodes, num_groups)


def tree_hist_ref(
    codes: jax.Array,  # (R, C) int32 bin codes of the sampled feature columns
    feat_ids: jax.Array,  # (C,) int32 global feature ids
    node: jax.Array,  # (R,) int32 level-node index; -1 drops the row
    g: jax.Array,
    h: jax.Array,
    num_nodes: int,
    num_feats: int,
    num_bins: int = 256,
) -> jax.Array:
    """→ (2, num_nodes, num_feats, num_bins) G/H histograms.

    XLA `segment_sum` lowering: updates apply in row-major (row, column)
    order — the same left-fold per segment as the host fit's `np.add.at`
    pass, so on CPU this lowering is *bit-identical* to the host
    histograms (the device-fit parity contract; see `core/gbdt.py`).
    G and H ride one two-column scatter (per-lane adds keep their order),
    halving the scatter passes — the dominant cost of a CPU device fit.
    """
    r, c = codes.shape
    seg = (node[:, None] * num_feats + feat_ids[None, :]) * num_bins + codes
    seg = jnp.where(node[:, None] >= 0, seg, -1).reshape(-1)
    size = num_nodes * num_feats * num_bins
    gg = jnp.broadcast_to(g.astype(jnp.float32)[:, None], (r, c)).reshape(-1)
    hh = jnp.broadcast_to(h.astype(jnp.float32)[:, None], (r, c)).reshape(-1)
    GH = jax.ops.segment_sum(jnp.stack([gg, hh], axis=1), seg, num_segments=size)
    return GH.T.reshape(2, num_nodes, num_feats, num_bins)


def tree_hist_matmul_ref(
    codes: jax.Array,
    feat_ids: jax.Array,
    node: jax.Array,
    g: jax.Array,
    h: jax.Array,
    num_nodes: int,
    num_feats: int,
    num_bins: int = 256,
    block_rows: int = 128,
) -> jax.Array:
    """Scatter-free `tree_hist_ref`: same histograms via the blocked
    one-hot matmul (allclose, NOT bit-identical — summation is tiled, not
    the host `np.add.at` left-fold).  Only used under the documented
    ``parity_relaxation`` flag; the default device fit keeps the
    bit-parity scatter lowering above.
    """
    r, c = codes.shape
    seg = (node[:, None] * num_feats + feat_ids[None, :]) * num_bins + codes
    seg = jnp.where(node[:, None] >= 0, seg, -1).reshape(-1)
    size = num_nodes * num_feats * num_bins
    gg = jnp.broadcast_to(g.astype(jnp.float32)[:, None], (r, c)).reshape(-1)
    hh = jnp.broadcast_to(h.astype(jnp.float32)[:, None], (r, c)).reshape(-1)
    vals = jnp.stack([gg, hh], axis=0)[None]  # (1, 2, R·C)
    GH = blocked_onehot_aggregate(vals, seg[None], size, block_rows=block_rows)
    return GH[0].reshape(2, num_nodes, num_feats, num_bins)


def predicate_eval_ref(
    cols: jax.Array, lo: jax.Array, hi: jax.Array, group_map: jax.Array
) -> tuple[jax.Array, jax.Array]:
    x = cols.astype(jnp.float32)  # (P, C, R)
    if lo.ndim == 1:
        lo = jnp.broadcast_to(lo[None], x.shape[:2])
        hi = jnp.broadcast_to(hi[None], x.shape[:2])
    clause = (x >= lo[:, :, None]) & (x < hi[:, :, None])  # (P, C, R)
    gm = group_map.astype(bool)  # (C, G) or (P, C, G)
    if gm.ndim == 2:
        gm = jnp.broadcast_to(gm[None], (x.shape[0],) + gm.shape)
    grouped = jnp.stack(
        [jnp.any(clause & gm[:, :, g, None], axis=1) for g in range(gm.shape[2])],
        axis=1,
    )  # (P, G, R)
    mask = jnp.all(grouped, axis=1).astype(jnp.float32)
    return mask, jnp.sum(mask, axis=1)
