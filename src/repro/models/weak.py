"""Weak learners for (federated) AdaBoost.

Three families, all pure-JAX and all trained against a *weighted* sample
distribution D_t(i) as the paper's boosting loop requires:

* ``stump``   — decision stumps: exhaustive search over (feature, threshold,
                polarity) minimizing weighted error.  The classical AdaBoost
                weak learner; compute hot-spot served by the
                ``stump_scan`` Pallas kernel (repro.kernels).
* ``logistic``— weighted logistic regression, a few Newton/GD steps.
* ``mlp``     — one-hidden-layer MLP trained by weighted SGD.

A weak learner is represented by a (params, predict_fn_name) pair where
params is a flat pytree of small arrays — this is exactly what crosses the
network at a synchronization event, so its byte size is what the paper's
communication accounting measures.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp

Array = jnp.ndarray

# clients whose thresholds one gather of the segmented grid makes
GRID_CLIENTS = 1024


# ---------------------------------------------------------------------------
# decision stump
# ---------------------------------------------------------------------------

def stump_thresholds(x: Array, n_thresholds: int = 16) -> Array:
    """Per-feature threshold grid from feature quantiles.  x: (N,F)."""
    qs = jnp.linspace(0.0, 1.0, n_thresholds + 2)[1:-1]
    return jnp.quantile(x, qs, axis=0).T          # (F, T)


@functools.partial(jax.jit, static_argnames=("backend",))
def fit_stump(x: Array, y: Array, w: Array, thresholds: Array,
              backend: str | None = None) -> Dict[str, Array]:
    """Weighted-error-optimal stump.

    x: (N,F); y: (N,) in {-1,+1}; w: (N,) distribution; thresholds: (F,T).
    Returns {"feature", "threshold", "polarity"} scalars.

    err(f,t,+) = sum_i w_i * [sign(x_if - t) != y_i]; polarity flips sign.
    ``backend=None`` keeps the jnp oracle (the training-loop default); a
    dispatcher backend name routes the scan through ``kernels.ops``.
    """
    if backend is None:
        from repro.kernels import ref as kref
        err_pos = kref.stump_scan_ref(x, y, w, thresholds)
    else:
        from repro.kernels import ops as kops
        err_pos = kops.stump_scan(x, y, w, thresholds, backend=backend)
    # (F,T) weighted error of polarity +1; polarity -1 error is 1 - err.
    return _pick_stump(err_pos, thresholds)


def predict_stump(p: Dict[str, Array], x: Array) -> Array:
    """-> (N,) margins in {-1,+1}."""
    xv = x[:, p["feature"]]
    return p["polarity"] * jnp.sign(xv - p["threshold"] + 1e-12)


def _pick_stump(err_pos: Array, thresholds: Array) -> Dict[str, Array]:
    """The argmin/polarity selection shared by the single and batched
    fitters: err_pos is the (F,T) weighted error grid of polarity +1."""
    err_neg = 1.0 - err_pos
    best_pos = jnp.unravel_index(jnp.argmin(err_pos), err_pos.shape)
    best_neg = jnp.unravel_index(jnp.argmin(err_neg), err_neg.shape)
    take_pos = err_pos[best_pos] <= err_neg[best_neg]
    f = jnp.where(take_pos, best_pos[0], best_neg[0])
    t_idx = jnp.where(take_pos, best_pos[1], best_neg[1])
    thr = thresholds[f, t_idx]
    pol = jnp.where(take_pos, 1.0, -1.0)
    return {"feature": f.astype(jnp.int32), "threshold": thr,
            "polarity": pol}


@functools.partial(jax.jit, static_argnames=("backend",))
def fit_stump_batched(x: Array, y: Array, w: Array, thresholds: Array,
                      backend: str | None = None) -> Dict[str, Array]:
    """Fit one stump per fleet slot in a single bucketed launch.

    x: (B,N,F); y, w: (B,N); thresholds: (B,F,T).  Returns
    {"feature", "threshold", "polarity"} arrays of shape (B,).  Slots
    padded with all-zero weights are fit to garbage and must be sliced
    off by the caller (their error grid is identically zero).

    Note: ``w`` rows need not be normalized per slot — the weighted-error
    *argmin* is scale-invariant, and the engine recomputes eps against the
    true distribution — but the convention is to pass D_t rows directly.
    """
    if backend is None:
        from repro.kernels import ref as kref
        err_pos = kref.stump_scan_batched_ref(x, y, w, thresholds)
    else:
        from repro.kernels import ops as kops
        err_pos = kops.stump_scan_batched(x, y, w, thresholds,
                                          backend=backend)
    return jax.vmap(_pick_stump)(err_pos, thresholds)


@functools.partial(jax.jit, static_argnames=("n_thresholds",))
def stump_thresholds_batched(x: Array, n_valid: Array,
                             n_thresholds: int = 16) -> Array:
    """Per-client quantile threshold grids for a padded fleet stack.

    x: (B,N,F) with slot b valid in rows [0, n_valid[b]); -> (B,F,T).
    Matches ``stump_thresholds`` (jnp.quantile, linear interpolation) on
    each slot's valid rows exactly: padding rows are replaced with +inf so
    they sink to the bottom of the per-slot sort and the quantile position
    is scaled by the true row count.
    """
    B, N, F = x.shape
    qs = jnp.linspace(0.0, 1.0, n_thresholds + 2)[1:-1]          # (T,)
    valid = jnp.arange(N)[None, :] < n_valid[:, None]            # (B,N)
    xs = jnp.sort(jnp.where(valid[:, :, None], x, jnp.inf), axis=1)
    pos = qs[None, :] * (n_valid[:, None].astype(jnp.float32) - 1.0)
    lo = jnp.floor(pos).astype(jnp.int32)                        # (B,T)
    hi = jnp.ceil(pos).astype(jnp.int32)
    frac = (pos - lo.astype(jnp.float32))[:, :, None]            # (B,T,1)
    take = lambda idx: jnp.take_along_axis(xs, idx[:, :, None], axis=1)
    grid = take(lo) * (1.0 - frac) + take(hi) * frac             # (B,T,F)
    return jnp.transpose(grid, (0, 2, 1))                        # (B,F,T)


@functools.partial(jax.jit, static_argnames=("n_thresholds",))
def stump_thresholds_segmented(x: Array, seg: Array, n_valid: Array,
                               n_thresholds: int = 16) -> Array:
    """Per-client quantile threshold grids over packed rows.

    x: (R,F) the rows of every client back to back; seg: (R,) the client
    of each row, non-decreasing, rows at or past B padding; n_valid: (B,)
    rows per client -> (B,T,Fp), the layout the ragged stump scan reads:
    Fp is F rounded up to whole 128-lane tiles, features past F hold 0
    and are never scanned.  With whole lanes the device's default layout
    of the grid is row-major, as the scan's kernel reads it; at F = 7,500
    it would be another, and the kernel's operand would be copied first.
    Matches ``stump_thresholds`` on each client's rows exactly: one sort
    by (client, value) per feature puts each client's rows in order at
    its offset (padding last), and the quantile positions are scaled by
    the client's own count.  The grid is gathered straight into (B,T,Fp);
    no transposed copy of it is made.
    """
    R = x.shape[0]
    x = jnp.pad(x, ((0, 0), (0, -x.shape[1] % 128)))  # zero features
    F = x.shape[1]
    B = n_valid.shape[0]
    qs = jnp.linspace(0.0, 1.0, n_thresholds + 2)[1:-1]          # (T,)
    keys = jnp.broadcast_to(seg.astype(jnp.int32)[:, None], (R, F))
    _, xs = jax.lax.sort((keys, x), dimension=0, num_keys=2)
    start = jnp.cumsum(n_valid) - n_valid                        # (B,)
    pos = qs[None, :] * (n_valid[:, None].astype(jnp.float32) - 1.0)
    lo = jnp.floor(pos).astype(jnp.int32)                        # (B,T)
    hi = jnp.ceil(pos).astype(jnp.int32)
    frac = (pos - lo.astype(jnp.float32))[:, :, None]            # (B,T,1)
    # gathered a chunk of clients at a time into the grid, so no
    # grid-sized gather is ever held beside it
    C = min(B, GRID_CLIENTS)

    def chunk(k, grid):
        # the last chunk is shifted back to end at B; its overlap with
        # the one before rewrites the same values
        c = jnp.minimum(k * C, B - C)
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, c, C)
        s0 = sl(start)[:, None]
        take = lambda idx: jnp.take(xs, (s0 + sl(idx)).reshape(-1), axis=0,
                                    mode="clip").reshape(C, n_thresholds, F)
        fr = sl(frac)
        part = take(lo) * (1.0 - fr) + take(hi) * fr
        return jax.lax.dynamic_update_slice_in_dim(grid, part, c, 0)

    grid = jnp.zeros((B, n_thresholds, F), x.dtype)
    return jax.lax.fori_loop(0, -(-B // C), chunk, grid)         # (B,T,Fp)


def _pick_ragged(best: Array, n_thresholds: int) -> Dict[str, Array]:
    """``_pick_stump`` from the ragged scan's per-client bests: best is
    (6,B) — least error, flat index f*T + t and threshold of polarity +1,
    then of -1.  Polarity +1 wins ties, as there."""
    take_pos = best[0] <= best[3]
    pick = lambda k: jnp.where(take_pos, best[k], best[k + 3])
    flat = pick(1).astype(jnp.int32)
    return {"feature": flat // n_thresholds, "threshold": pick(2),
            "polarity": jnp.where(take_pos, 1.0, -1.0)}


@functools.partial(jax.jit, static_argnames=("backend",))
def fit_stump_ragged(x: Array, y: Array, w: Array, seg: Array,
                     thresholds: Array, backend: str | None = None
                     ) -> Dict[str, Array]:
    """The packed sibling of :func:`fit_stump_batched`: one stump per
    client from rows of every client back to back.

    x: (R,F); y, w, seg: (R,), seg the client of each row (non-decreasing,
    rows at or past B padding with w = 0); thresholds: (B,T,Fp), Fp >= F,
    as :func:`stump_thresholds_segmented` emits it (features past F are
    not scanned).  Returns the stumps ``fit_stump_batched`` returns for
    the same clients, rows and grids, as (B,) arrays; no (B,F,T) error
    grid is made on the kernel path.
    """
    if backend is None:
        from repro.kernels import ref as kref
        best = kref.stump_scan_ragged_ref(x, y, w, seg, thresholds)
    else:
        from repro.kernels import ops as kops
        best = kops.stump_scan_ragged(x, y, w, seg, thresholds,
                                      backend=backend)
    return _pick_ragged(best, thresholds.shape[1])


STUMP_BYTES = 3 * 4   # feature idx + threshold + polarity


# ---------------------------------------------------------------------------
# weighted logistic regression
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("steps",))
def fit_logistic(x: Array, y: Array, w: Array, key, steps: int = 50,
                 lr: float = 0.5) -> Dict[str, Array]:
    N, F = x.shape
    y01 = (y + 1.0) / 2.0

    def loss(params):
        z = x @ params["w"] + params["b"]
        p = jax.nn.sigmoid(z)
        ll = y01 * jnp.log(p + 1e-9) + (1 - y01) * jnp.log(1 - p + 1e-9)
        return -jnp.sum(w * ll)

    params = {"w": jnp.zeros((F,)), "b": jnp.zeros(())}
    g = jax.grad(loss)

    def step(params, _):
        grads = g(params)
        return jax.tree.map(lambda p, gr: p - lr * gr, params, grads), None

    params, _ = jax.lax.scan(step, params, None, length=steps)
    return params


def predict_logistic(p: Dict[str, Array], x: Array) -> Array:
    return jnp.tanh(x @ p["w"] + p["b"])


# ---------------------------------------------------------------------------
# tiny MLP
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("steps", "hidden"))
def fit_mlp(x: Array, y: Array, w: Array, key, steps: int = 80,
            hidden: int = 16, lr: float = 0.1) -> Dict[str, Array]:
    N, F = x.shape
    k1, k2 = jax.random.split(key)
    params = {
        "w1": jax.random.normal(k1, (F, hidden)) / jnp.sqrt(F),
        "b1": jnp.zeros((hidden,)),
        "w2": jax.random.normal(k2, (hidden,)) / jnp.sqrt(hidden),
        "b2": jnp.zeros(()),
    }

    def fwd(params, x):
        h = jnp.tanh(x @ params["w1"] + params["b1"])
        return jnp.tanh(h @ params["w2"] + params["b2"])

    def loss(params):
        m = fwd(params, x)
        return jnp.sum(w * jnp.square(m - y))

    g = jax.grad(loss)

    def step(params, _):
        grads = g(params)
        return jax.tree.map(lambda p, gr: p - lr * gr, params, grads), None

    params, _ = jax.lax.scan(step, params, None, length=steps)
    return params


def predict_mlp(p: Dict[str, Array], x: Array) -> Array:
    h = jnp.tanh(x @ p["w1"] + p["b1"])
    return jnp.tanh(h @ p["w2"] + p["b2"])


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class WeakLearnerSpec:
    name: str
    fit: Callable              # (x, y, w, key) -> params
    predict: Callable          # (params, x) -> margins (N,)
    param_bytes: Callable      # params -> bytes on the wire


def _pytree_bytes(p) -> int:
    return int(sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(p)))


def get_weak_learner(name: str, n_thresholds: int = 16,
                     policy=None) -> WeakLearnerSpec:
    """``policy`` (a :class:`repro.kernels.KernelPolicy`) routes the stump
    scan through the kernel dispatcher, re-resolved per fit call so env or
    calibration changes take effect without rebuilding the spec (each
    resolution lands in ``policy.choices``); ``None`` keeps the jnp
    oracle."""
    if name == "stump":
        def fit(x, y, w, key):
            thr = stump_thresholds(x, n_thresholds)
            if policy is None:
                return fit_stump(x, y, w, thr)
            from repro.kernels import dispatch as kdispatch
            backend = policy.resolve(
                "stump_scan", kdispatch.bucket_of("stump_scan",
                                                  (x, y, w, thr))).name
            return fit_stump(x, y, w, thr, backend=backend)
        return WeakLearnerSpec("stump", fit, predict_stump,
                               lambda p: STUMP_BYTES)
    if name == "logistic":
        return WeakLearnerSpec("logistic", fit_logistic, predict_logistic,
                               _pytree_bytes)
    if name == "mlp":
        return WeakLearnerSpec("mlp", fit_mlp, predict_mlp, _pytree_bytes)
    raise KeyError(name)
