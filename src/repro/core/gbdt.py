"""Histogram-based gradient-boosted decision trees (the paper's XGBoost).

The paper trains k=4 XGBoost regressors per workload (§4.3, Appendix B.2).
XGBoost is not available in this environment — and more importantly the
*prediction* path runs inside the query optimizer, which in our framework is
JAX — so we implement an XGBoost-class histogram GBDT ourselves:

  * **Fit** (offline): features are quantile-binned to uint8 codes
    (256 bins).  Trees are grown level-wise to a fixed depth; split search
    computes per-(node, feature, bin) gradient/hessian histograms and picks
    the split maximizing the second-order gain
    GL²/(HL+λ) + GR²/(HR+λ) − G²/(H+λ).  Squared-error loss
    (g = pred − y, h = 1), matching Appendix B.2.  Fit runs on either
    execution backend (`repro/backends.py`): ``host`` is vectorized numpy;
    ``device`` scatters the histograms through the `kernels/tree_hist`
    layer and runs split search + node partition as one traced program per
    tree (`lax.fori_loop` over levels), shape-bucketed so the jit cache is
    bounded (`fit_census`).
  * **Predict** (query time, JAX): the forest is exported as dense arrays
    (feature id / bin threshold per internal node, values per leaf) and
    traversed with a `lax.fori_loop` over depth — fully jittable, so the
    whole funnel (Algorithm 2) can execute on an accelerator.

**Backend parity contract.**  Both backends accumulate histograms as f32
left folds in row-major (row, sampled-column) order — `np.add.at` on the
host, XLA `segment_sum` on the device (same per-segment application
order) — run the split search with the identical f32 expression DAG, and
apply the boosting update as a separately-rounded ``lr·leaf`` host-side
step (XLA would contract the fused multiply-add into an FMA, which numpy
cannot express).  The exported forest is therefore *bit-identical* across
backends on the same binned codes (tested elementwise), so the predict
path and `core/funnel.py` calibration are backend-independent.  On a
real TPU the Pallas kernel's MXU contraction reorders the sums, and where
split gains nearly tie that picks other splits, which every later tree
follows: there the forest is neither bitwise nor allclose to the host's
(`chip_smoke.py` reports how far apart they land).

``fit_gbdt(..., parity_relaxation=True)`` (surfaced as
``ExecOptions.parity_relaxation``) trades that contract for speed: the
boosting update stays device-resident across trees (`_fit_tree_resident`
computes g/h from the on-device predictions and applies ``pred + lr·leaf``
in-trace, so XLA emits the FMA) and histograms lower scatter-free through
the blocked one-hot matmul (`tree_hist_matmul_ref`).  The relaxed fit is
allclose to the host fit — never bitwise — and stays opt-in (default off).

Fixed-depth complete trees keep both paths branch-free; unused subtrees are
padded (gain −inf splits are frozen into "always left" with value-copying
leaves), which costs a few wasted nodes but keeps the TPU path regular —
the same adaptation argument as the rest of DESIGN §3.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.clustering import bucket_size as _bucket
from repro.kernels.telemetry import TraceRegistry

NUM_BINS = 256  # uint8 codes

TRACES = TraceRegistry("gbdt")


# --------------------------------------------------------------------------
# quantile binning
# --------------------------------------------------------------------------
@dataclasses.dataclass
class Binner:
    """Per-feature quantile bin edges; code = #edges strictly below value."""

    edges: np.ndarray  # (n_features, NUM_BINS - 1)

    @staticmethod
    def fit(x: np.ndarray, num_bins: int = NUM_BINS) -> "Binner":
        qs = np.linspace(0.0, 1.0, num_bins + 1)[1:-1]
        edges = np.quantile(x, qs, axis=0).T  # (F, B-1)
        return Binner(np.ascontiguousarray(edges))

    def _lut(self):
        """Padded flat edges for the branchless search (built once, cached)."""
        lut = getattr(self, "_lut_cache", None)
        if lut is None:
            f, m = self.edges.shape
            width = 1 << m.bit_length()  # power of two > m ⇒ no bounds checks
            ep = np.full((f, width), np.inf)
            ep[:, :m] = self.edges
            lut = (
                ep.ravel(),
                (np.arange(f, dtype=np.int64) * width)[:, None],
                np.ascontiguousarray(ep[:, width // 2 - 1])[:, None],
                width,
            )
            self._lut_cache = lut
        return lut

    def transform(self, x: np.ndarray) -> np.ndarray:
        """Vectorized `searchsorted(edges[f], x[:, f], side="right")`.

        One branchless binary search over every (row, feature) cell at
        once — ⌈log₂ 256⌉ gather/compare passes on the whole matrix
        instead of a Python loop over features.  Edges are padded to a
        power of two with +inf so no probe needs a bounds check, and the
        first probe is a broadcast compare against the cached midpoint
        column (no gather).  Invariant: pos = #{i : edges[f, i] <= v} —
        exactly bisect-right; NaN sorts past every edge, matching
        `np.searchsorted`.
        """
        flat, off, mid, width = self._lut()
        m = self.edges.shape[1]
        xt = x.T  # (F, N)
        pos = np.where(mid <= xt, np.int64(width // 2), np.int64(0))
        b = width // 4
        while b:
            ev = flat[pos + (b - 1) + off]
            pos += np.where(ev <= xt, b, 0)
            b >>= 1
        np.minimum(pos, m, out=pos)
        pos[np.isnan(xt)] = m
        return np.ascontiguousarray(pos.astype(np.uint8).T)

    def transform_jnp(self, x: jax.Array) -> jax.Array:
        edges = jnp.asarray(self.edges)  # (F, B-1)
        return jax.vmap(
            lambda col, e: jnp.searchsorted(e, col, side="right"), in_axes=(1, 0), out_axes=1
        )(x, edges).astype(jnp.int32)


# --------------------------------------------------------------------------
# forest container (dense, JAX-friendly)
# --------------------------------------------------------------------------
@dataclasses.dataclass
class Forest:
    """Complete binary trees of fixed depth.

    feat[t, i] / thr[t, i]: internal node i of tree t splits on
    ``code[feat] <= thr`` (left) vs ``>`` (right).  leaf[t, j] are leaf
    values in level order.  Prediction = base + lr * Σ_t leaf_t(x).
    """

    depth: int
    learning_rate: float
    base: float
    feat: np.ndarray  # (T, 2**depth - 1) int32
    thr: np.ndarray  # (T, 2**depth - 1) int32 (bin code threshold)
    leaf: np.ndarray  # (T, 2**depth) float32
    binner: Binner

    @property
    def num_trees(self) -> int:
        return self.feat.shape[0]

    # ---- host predict ----------------------------------------------------
    def predict_codes(self, codes: np.ndarray) -> np.ndarray:
        n = codes.shape[0]
        out = np.full(n, self.base, np.float64)
        for t in range(self.num_trees):
            idx = np.zeros(n, np.int64)
            for _ in range(self.depth):
                f = self.feat[t, idx]
                go_right = codes[np.arange(n), f] > self.thr[t, idx]
                idx = 2 * idx + 1 + go_right
            out += self.learning_rate * self.leaf[t, idx - (2**self.depth - 1)]
        return out

    def predict(self, x: np.ndarray) -> np.ndarray:
        return self.predict_codes(self.binner.transform(x))

    # ---- JAX predict -----------------------------------------------------
    def as_jnp(self):
        return (
            jnp.asarray(self.feat),
            jnp.asarray(self.thr),
            jnp.asarray(self.leaf),
            jnp.asarray(self.binner.edges),
        )


@partial(jax.jit, static_argnames=("depth",))
def forest_predict_jnp(
    feat: jax.Array,  # (T, I)
    thr: jax.Array,  # (T, I)
    leaf: jax.Array,  # (T, L)
    edges: jax.Array,  # (F, B-1)
    x: jax.Array,  # (N, F) raw features
    depth: int,
    base: float,
    learning_rate: float,
) -> jax.Array:
    codes = jax.vmap(
        lambda col, e: jnp.searchsorted(e, col, side="right"), in_axes=(1, 0), out_axes=1
    )(x, edges).astype(jnp.int32)

    def tree(carry, tf):
        f, t, lv = tf

        def step(_, idx):
            fsel = f[idx]  # (N,)
            go_right = jnp.take_along_axis(codes, fsel[:, None], axis=1)[:, 0] > t[idx]
            return 2 * idx + 1 + go_right.astype(jnp.int32)

        idx = jax.lax.fori_loop(0, depth, step, jnp.zeros(x.shape[0], jnp.int32))
        return carry + lv[idx - (2**depth - 1)], None

    out, _ = jax.lax.scan(tree, jnp.zeros(x.shape[0], jnp.float32), (feat, thr, leaf))
    return base + learning_rate * out


# --------------------------------------------------------------------------
# fitting — shared preamble
# --------------------------------------------------------------------------
def _sample_plan(rng, n, n_feat, num_trees, rowsample, colsample):
    """Per-tree (row, feature) subsets; one rng consumption order for both
    backends so a host fit and a device fit draw identical subsamples."""
    plan = []
    for _ in range(num_trees):
        if rowsample < 1.0:
            size = min(n, max(32, int(rowsample * n)))
            rows = np.sort(rng.choice(n, size=size, replace=False))
        else:
            rows = np.arange(n)
        if colsample < 1.0:
            fs = np.sort(rng.choice(n_feat, size=max(1, int(colsample * n_feat)), replace=False))
        else:
            fs = np.arange(n_feat)
        plan.append((rows, fs))
    return plan


def _route_all(codes, feats_t, thrs_t, depth):
    """Leaf index of every row under one tree (host, level loop)."""
    n = codes.shape[0]
    full = np.zeros(n, np.int64)
    base_id = 0
    for level in range(depth):
        ids = base_id + np.arange(2**level)
        gr = codes[np.arange(n), feats_t[ids][full]] > thrs_t[ids][full]
        full = 2 * full + gr
        base_id += 2**level
    return full


# --------------------------------------------------------------------------
# host backend (canonical f32 numpy)
# --------------------------------------------------------------------------
def _fit_host(codes, y, w, pred, plan, feats, thrs, leaves, *, depth, lr, lam, mcw):
    """Level-wise fit on numpy.  All reductions are f32 left folds in row
    order (`np.add.at`) and the gain DAG is pure f32 — the bit-parity
    reference the device backend is tested against."""
    num_trees = feats.shape[0]
    n_feat = codes.shape[1]
    for t in range(num_trees):
        rows, fs = plan[t]
        # full-sample trees read the matrix directly (fancy-index copies it)
        codes_t = codes if len(rows) == codes.shape[0] else codes[rows]
        nt = codes_t.shape[0]
        arangen = np.arange(nt)
        g = (w * (pred - y))[rows]  # f32; dL/dpred for 0.5*(pred-y)^2
        h = w[rows].copy()
        node = np.zeros(nt, np.int64)  # node index within current level
        node_base = 0  # first node id of current level in the tree arrays
        for level in range(depth):
            n_nodes = 2**level
            # gradient histograms: (nodes, F, B) — one f32 scatter pass per
            # level; features outside `fs` keep zero histograms (dead).
            flat_idx = (
                (node[:, None] * n_feat + fs[None, :]) * NUM_BINS + codes_t[:, fs]
            ).reshape(-1)
            size = n_nodes * n_feat * NUM_BINS
            G = np.zeros(size, np.float32)
            H = np.zeros(size, np.float32)
            np.add.at(G, flat_idx, np.repeat(g, fs.size))
            np.add.at(H, flat_idx, np.repeat(h, fs.size))
            G = G.reshape(n_nodes, n_feat, NUM_BINS)
            H = H.reshape(n_nodes, n_feat, NUM_BINS)
            GL = G.cumsum(axis=2)
            HL = H.cumsum(axis=2)
            Gt = GL[:, :, -1:]
            Ht = HL[:, :, -1:]
            GR, HR = Gt - GL, Ht - HL
            gain = GL * GL / (HL + lam) + GR * GR / (HR + lam) - Gt * Gt / (Ht + lam)
            ok = (HL >= mcw) & (HR >= mcw)
            gain = np.where(ok, gain, -np.inf)
            # exclude the last bin (right side empty by construction)
            gain[:, :, -1] = -np.inf
            flat = gain.reshape(n_nodes, -1)
            best = flat.argmax(axis=1)
            best_gain = flat[np.arange(n_nodes), best]
            bf = (best // NUM_BINS).astype(np.int32)
            bb = (best % NUM_BINS).astype(np.int32)
            # nodes with no valid split: freeze to always-left (thr = NUM_BINS)
            dead = ~np.isfinite(best_gain)
            bf[dead] = 0
            bb_store = np.where(dead, NUM_BINS, bb).astype(np.int32)
            ids = node_base + np.arange(n_nodes)
            feats[t, ids] = bf
            thrs[t, ids] = bb_store
            go_right = codes_t[arangen, bf[node]] > bb_store[node]
            node = 2 * node + go_right
            node_base += n_nodes
        # leaf values (from the subsample)
        Gs = np.zeros(2**depth, np.float32)
        Hs = np.zeros(2**depth, np.float32)
        np.add.at(Gs, node, g)
        np.add.at(Hs, node, h)
        lv = -Gs / (Hs + lam)
        leaves[t] = lv
        # route ALL rows for the prediction update; lr·leaf is rounded once
        # before the add (the FMA-free form the device backend also uses)
        scaled = np.float32(lr) * lv
        if len(rows) < codes.shape[0]:
            pred += scaled[_route_all(codes, feats[t], thrs[t], depth)]
        else:
            pred += scaled[node]


# --------------------------------------------------------------------------
# device backend (kernel histograms + jitted split search)
# --------------------------------------------------------------------------
def _cumsum_seq(x: jax.Array) -> jax.Array:
    """Left-fold cumsum over the last axis (bit-matches `np.cumsum`; XLA's
    native cumsum lowers to a log-depth scan with a different association)."""

    def body(b, carry):
        run, out = carry
        run = run + x[..., b]
        return run, out.at[..., b].set(run)

    _, out = jax.lax.fori_loop(
        0, x.shape[-1], body, (jnp.zeros(x.shape[:-1], x.dtype), jnp.zeros_like(x))
    )
    return out


def _tree_levels(codes, rows, fs, g, h, lam, mcw, *, depth, use_ref, relaxed=False):
    """Shared level-wise split search + leaf values for one boosting tree.

    codes (Npad, F) int32 resident bin codes; rows (ntp,) int32 sampled row
    ids (-1 = pad, dropped from every reduction); fs (fc,) int32 sampled
    feature ids; g/h (ntp,) f32 aligned with `rows`.  ``relaxed`` routes
    the histograms through the scatter-free blocked-matmul lowering
    (allclose-only; reachable via `ExecOptions.parity_relaxation`).
    """
    from repro.kernels import ops

    npad, n_feat = codes.shape
    nmax = 2 ** (depth - 1)
    n_int = 2**depth - 1
    valid = rows >= 0
    codes_rows = codes[jnp.maximum(rows, 0)]  # (ntp, F)
    codes_sub = codes_rows[:, fs]  # (ntp, fc)

    def level(lvl, carry):
        node, feats, thrs = carry
        node_m = jnp.where(valid, node, -1)
        GH = ops.tree_hist_op(
            codes_sub, fs, node_m, g, h, nmax, n_feat, NUM_BINS,
            use_ref=use_ref, relaxed=relaxed,
        )
        GHL = _cumsum_seq(GH)  # (2, nmax, F, B) left-fold prefix sums
        GL, HL = GHL[0], GHL[1]
        Gt = GL[..., -1:]
        Ht = HL[..., -1:]
        GR, HR = Gt - GL, Ht - HL
        gain = GL * GL / (HL + lam) + GR * GR / (HR + lam) - Gt * Gt / (Ht + lam)
        ok = (HL >= mcw) & (HR >= mcw)
        gain = jnp.where(ok, gain, -jnp.inf)
        gain = gain.at[..., -1].set(-jnp.inf)
        flat = gain.reshape(nmax, -1)
        best = jnp.argmax(flat, axis=1)
        best_gain = jnp.take_along_axis(flat, best[:, None], axis=1)[:, 0]
        bf = (best // NUM_BINS).astype(jnp.int32)
        bb = (best % NUM_BINS).astype(jnp.int32)
        dead = ~jnp.isfinite(best_gain)
        bf = jnp.where(dead, 0, bf)
        bbs = jnp.where(dead, NUM_BINS, bb).astype(jnp.int32)
        # this level occupies tree slots [2^l - 1, 2^{l+1} - 1); histogram
        # slots past the level's width are all-dead and go to the dump slot
        n_nodes = 1 << lvl
        slot = jnp.arange(nmax, dtype=jnp.int32)
        write_ix = jnp.where(slot < n_nodes, n_nodes - 1 + slot, n_int)
        feats = feats.at[write_ix].set(bf)
        thrs = thrs.at[write_ix].set(bbs)
        code_at = jnp.take_along_axis(codes_rows, bf[node][:, None], axis=1)[:, 0]
        node = 2 * node + (code_at > bbs[node]).astype(jnp.int32)
        return node, feats, thrs

    node0 = jnp.zeros(rows.shape[0], jnp.int32)
    feats0 = jnp.zeros(n_int + 1, jnp.int32)  # +1 = dump slot for dead pads
    thrs0 = jnp.full(n_int + 1, NUM_BINS, jnp.int32)
    node, feats, thrs = jax.lax.fori_loop(0, depth, level, (node0, feats0, thrs0))

    leaf_seg = jnp.where(valid, node, -1)
    GHs = jax.ops.segment_sum(jnp.stack([g, h], axis=1), leaf_seg, num_segments=2**depth)
    lv = -GHs[:, 0] / (GHs[:, 1] + lam)

    def rstep(lvl, full):
        nb = (1 << lvl) - 1
        idx = nb + full
        code_at = jnp.take_along_axis(codes, feats[idx][:, None], axis=1)[:, 0]
        return 2 * full + (code_at > thrs[idx]).astype(jnp.int32)

    full = jax.lax.fori_loop(0, depth, rstep, jnp.zeros(npad, jnp.int32))
    return feats[:n_int], thrs[:n_int], lv, full


@partial(jax.jit, static_argnames=("depth", "use_ref"))
def _fit_tree_device(codes, rows, fs, g, h, lam, mcw, *, depth, use_ref):
    """One boosting tree as a single traced program (bit-parity default).

    Returns the tree's dense arrays plus the leaf index of every (padded)
    row — the boosting update itself happens on the host so
    ``pred + lr·leaf`` stays two IEEE roundings on both backends (XLA
    would fuse it into an FMA).
    """
    npad, n_feat = codes.shape
    TRACES.note("fit_tree", npad, n_feat, rows.shape[0], fs.shape[0], depth)
    return _tree_levels(codes, rows, fs, g, h, lam, mcw, depth=depth, use_ref=use_ref)


@partial(jax.jit, static_argnames=("depth", "use_ref"))
def _fit_tree_resident(codes, rows, fs, y, w, pred, lam, mcw, lr, *, depth, use_ref):
    """`parity_relaxation` tree program: gradients AND the boosting update
    stay device-resident, cutting the per-tree host↔device round trip.

    ``pred + lr·lv[full]`` inside one traced program lets XLA contract the
    multiply-add into an FMA numpy cannot express, and the histograms ride
    the scatter-free blocked matmul — the fit is allclose to the host
    forest, NOT bitwise equal (see `ExecOptions.parity_relaxation`).
    """
    npad, n_feat = codes.shape
    TRACES.note("fit_tree_res", npad, n_feat, rows.shape[0], fs.shape[0], depth)
    valid = rows >= 0
    rix = jnp.maximum(rows, 0)
    gfull = w * (pred - y)
    g = jnp.where(valid, gfull[rix], jnp.float32(0))
    h = jnp.where(valid, w[rix], jnp.float32(0))
    feats, thrs, lv, full = _tree_levels(
        codes, rows, fs, g, h, lam, mcw, depth=depth, use_ref=use_ref, relaxed=True
    )
    pred = pred + lr * lv[full]
    return feats, thrs, lv, pred


def _fit_device(
    codes, y, w, pred, plan, feats, thrs, leaves, *, depth, lr, lam, mcw, use_ref,
    parity_relaxation=False,
):
    n, n_feat = codes.shape
    npad = _bucket(n)
    codes_dev = jnp.asarray(
        np.pad(codes.astype(np.int32), ((0, npad - n), (0, 0)))
    )
    lam_d = jnp.float32(lam)
    mcw_d = jnp.float32(mcw)
    lr32 = np.float32(lr)
    if parity_relaxation:
        # device-resident boosting: y/w/pred live on device for the whole
        # forest; each tree reads the running pred and writes it back
        # in-trace (one transfer in, one out, per FIT instead of per tree)
        y_d = jnp.asarray(np.pad(y.astype(np.float32), (0, npad - n)))
        w_d = jnp.asarray(np.pad(w.astype(np.float32), (0, npad - n)))
        pred_d = jnp.asarray(np.pad(pred.astype(np.float32), (0, npad - n)))
        lr_d = jnp.float32(lr)
        for t in range(feats.shape[0]):
            rows, fs = plan[t]
            ntp = _bucket(rows.shape[0])
            rows_p = np.full(ntp, -1, np.int32)
            rows_p[: rows.shape[0]] = rows
            feat_t, thr_t, lv, pred_d = _fit_tree_resident(
                codes_dev,
                jnp.asarray(rows_p),
                jnp.asarray(fs.astype(np.int32)),
                y_d, w_d, pred_d, lam_d, mcw_d, lr_d,
                depth=depth, use_ref=use_ref,
            )
            feats[t] = np.asarray(feat_t)
            thrs[t] = np.asarray(thr_t)
            leaves[t] = np.asarray(lv)
        pred[:] = np.asarray(pred_d)[:n]
        return
    for t in range(feats.shape[0]):
        rows, fs = plan[t]
        nt = rows.shape[0]
        ntp = _bucket(nt)
        rows_p = np.full(ntp, -1, np.int32)
        rows_p[:nt] = rows
        gfull = w * (pred - y)  # f32, identical elementwise to the host DAG
        gp = np.zeros(ntp, np.float32)
        gp[:nt] = gfull[rows]
        hp = np.zeros(ntp, np.float32)
        hp[:nt] = w[rows]
        feat_t, thr_t, lv, full = _fit_tree_device(
            codes_dev,
            jnp.asarray(rows_p),
            jnp.asarray(fs.astype(np.int32)),
            jnp.asarray(gp),
            jnp.asarray(hp),
            lam_d,
            mcw_d,
            depth=depth,
            use_ref=use_ref,
        )
        feats[t] = np.asarray(feat_t)
        thrs[t] = np.asarray(thr_t)
        lv = np.asarray(lv)
        leaves[t] = lv
        scaled = lr32 * lv
        pred += scaled[np.asarray(full)[:n]]


def fit_census(
    n: int, n_feat: int, depth: int, rowsample: float, colsample: float,
    parity_relaxation: bool = False,
) -> set:
    """Expected `TRACES` keys for one device fit — the compile upper bound.

    One tree program per (row-bucket, feature-count, subsample-bucket,
    colsample-width, depth); every tree of a fit shares it, so a whole
    forest compiles exactly once per census entry.
    """
    nt = n if rowsample >= 1.0 else min(n, max(32, int(rowsample * n)))
    fc = n_feat if colsample >= 1.0 else max(1, int(colsample * n_feat))
    kind = "fit_tree_res" if parity_relaxation else "fit_tree"
    return {(kind, _bucket(n), n_feat, _bucket(nt), fc, depth)}


# --------------------------------------------------------------------------
# public fit entry point
# --------------------------------------------------------------------------
def fit_gbdt(
    x: np.ndarray,
    y: np.ndarray,
    *,
    num_trees: int = 60,
    depth: int = 5,
    learning_rate: float = 0.3,
    lam: float = 1.0,
    min_child_weight: float = 4.0,
    sample_weight: np.ndarray | None = None,
    binner: Binner | None = None,
    seed: int = 0,
    colsample: float = 1.0,
    rowsample: float = 1.0,
    backend: str | None = None,
    codes: np.ndarray | None = None,
    parity_relaxation: bool = False,
) -> Forest:
    """Squared-error histogram GBDT (level-wise, fixed depth).

    ``backend`` follows `repro.backends` resolution (explicit argument >
    ``REPRO_EVAL_BACKEND`` > platform default); both backends export
    bit-identical forests for the same inputs (see module docstring).
    ``codes`` accepts the precomputed `binner.transform(x)` so callers
    fitting several forests on one matrix (the funnel's k models) bin it
    once instead of per fit.  ``parity_relaxation`` (device backend only)
    keeps the boosting update device-resident — allclose to the host
    forest, not bitwise (see `ExecOptions.parity_relaxation`).
    """
    from repro.backends import kernels_use_ref, resolve_backend

    backend = resolve_backend(backend)
    x = np.asarray(x, np.float64)
    y = np.asarray(y, np.float32)
    n, n_feat = x.shape
    w = (
        np.ones(n, np.float32)
        if sample_weight is None
        else np.asarray(sample_weight, np.float32)
    )
    if codes is None:
        binner = binner or Binner.fit(x)
        codes = binner.transform(x)
    elif binner is None:
        raise ValueError("precomputed codes require the binner that made them")
    codes = np.asarray(codes, np.int64)  # (n, F)
    rng = np.random.default_rng(seed)
    plan = _sample_plan(rng, n, n_feat, num_trees, rowsample, colsample)

    base = float(np.average(y.astype(np.float64), weights=w.astype(np.float64)))
    pred = np.full(n, base, np.float32)
    n_internal = 2**depth - 1
    feats = np.zeros((num_trees, n_internal), np.int32)
    thrs = np.full((num_trees, n_internal), NUM_BINS, np.int32)  # always-left default
    leaves = np.zeros((num_trees, 2**depth), np.float32)

    kw = dict(
        depth=depth,
        lr=learning_rate,
        lam=np.float32(lam),
        mcw=np.float32(min_child_weight),
    )
    if backend == "device":
        _fit_device(
            codes, y, w, pred, plan, feats, thrs, leaves,
            use_ref=kernels_use_ref(), parity_relaxation=parity_relaxation, **kw,
        )
    else:
        _fit_host(codes, y, w, pred, plan, feats, thrs, leaves, **kw)

    return Forest(depth, learning_rate, base, feats, thrs, leaves, binner)


def importance_gain(forest: Forest, x: np.ndarray, y: np.ndarray) -> np.ndarray:
    """Per-feature total gain (paper Fig 5 'gain' metric, recomputed).

    We re-derive gain on the training data by walking each tree and
    accumulating the achieved impurity reduction at every internal node,
    attributed to the node's split feature.
    """
    codes = forest.binner.transform(np.asarray(x, np.float64)).astype(np.int64)
    y = np.asarray(y, np.float64)
    n, n_feat = codes.shape
    out = np.zeros(n_feat)
    pred = np.full(n, forest.base)
    lam = 1.0
    for t in range(forest.num_trees):
        g = pred - y
        h = np.ones(n)
        node = np.zeros(n, np.int64)
        node_base = 0
        for level in range(forest.depth):
            n_nodes = 2**level
            ids = node_base + np.arange(n_nodes)
            Gs = np.zeros(n_nodes)
            Hs = np.zeros(n_nodes)
            np.add.at(Gs, node, g)
            np.add.at(Hs, node, h)
            f = forest.feat[t, ids]
            thr = forest.thr[t, ids]
            go_right = codes[np.arange(n), f[node]] > thr[node]
            GL = np.zeros(n_nodes)
            HL = np.zeros(n_nodes)
            np.add.at(GL, node[~go_right], g[~go_right])
            np.add.at(HL, node[~go_right], h[~go_right])
            GR, HR = Gs - GL, Hs - HL
            gain = GL**2 / (HL + lam) + GR**2 / (HR + lam) - Gs**2 / (Hs + lam)
            live = thr < NUM_BINS
            np.add.at(out, f[live], np.maximum(gain[live], 0.0))
            node = 2 * node + go_right
            node_base += n_nodes
        idx = node
        lv = forest.leaf[t, idx]
        pred = pred + forest.learning_rate * lv
    return out
