"""Mesh-integrated federated async boosting: the paper's technique as a
first-class pjit/shard_map feature of the framework.

Clients are groups along a mesh axis (``data`` single-pod; ``pod`` is the
institution axis in multi-pod mode).  Everything — stump fitting, buffering,
the adaptive interval, compensation, the sync collective — runs *inside*
one compiled step:

* the synchronization interval I_t is jit-carried state; the sync fires via
  ``lax.cond(counter - last_sync >= floor(I_t), sync, local)``.  Because the
  interval/counter are replicated, the predicate is uniform across shards —
  the TPU-idiomatic realisation of "asynchrony" on a synchronous SPMD
  machine (DESIGN.md §4): scheduled skipping of the collective, with
  staleness handled by compensation exactly as in the paper.
* a sync is an ``all_gather`` of the fixed-capacity client buffers over the
  client axis — weak-learner traffic only, exactly the traffic the paper
  schedules.
* the global validation error that drives eq. (1) is a ``psum`` of local
  margin errors over the client axis.

Weak learners here are decision stumps (params = 4 floats), so a buffer of
B stumps from K clients is a (K, B, 4) gather — bytes visible in the HLO
and counted by the §Roofline collective parser.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.configs.paper_fedboost import FedBoostConfig
from repro.core import scheduling
from repro.core.compensation import adaboost_alpha, compensate

Array = jnp.ndarray

# weighted errors this close to the best stump's count as ties
SELECT_TOL = 1e-5


class FedMeshState(NamedTuple):
    """Replicated-logical state; leaves with a leading client axis are
    sharded over the client mesh axis."""
    # per-client (leading axis = n_clients, sharded)
    D: Array                 # (K, n_local) sample distributions
    buf_params: Array        # (K, cap, 4) feature,thr,polarity,local_eps
    buf_stamp: Array         # (K, cap) round trained
    buf_count: Array         # (K,) entries in buffer
    # replicated ensemble
    ens_params: Array        # (T_cap, 4)
    ens_alpha: Array         # (T_cap,)
    ens_count: Array         # ()
    # replicated margins of the ensemble on the (sharded) validation slice
    val_margin: Array        # (K, n_val_local)
    # controller
    interval: Array          # () f32
    prev_err: Array          # ()
    counter: Array           # () rounds done
    last_sync: Array         # ()
    sync_count: Array        # ()
    key: Array


def _predict_stumps(params: Array, x: Array) -> Array:
    """params: (M,4); x: (n,F) -> margins (M,n) in {-1,+1}."""
    feat = params[:, 0].astype(jnp.int32)
    thr = params[:, 1]
    pol = params[:, 2]
    xv = x[:, feat]                               # (n, M)
    return (pol[None, :] * jnp.sign(xv - thr[None, :] + 1e-12)).T


def _fit_stump_local(x: Array, y: Array, D: Array, thresholds: Array
                     ) -> Tuple[Array, Array]:
    """Returns (params (4,), eps scalar).  Pure jnp so it shard_maps.

    Candidates whose error is within ``SELECT_TOL`` of the best are ties,
    broken by the lowest index, polarity +1 first.  Two stumps that
    misclassify equal weight differ only by the rounding of their sums,
    and that rounding follows the summation order, which differs between
    backends — so without the tolerance the chip and the host pick
    different stumps from the same data."""
    pred = jnp.where(x[:, :, None] > thresholds[None, :, :], 1.0, -1.0)
    miss = (pred != y[:, None, None]).astype(jnp.float32)
    # HIGHEST: at default precision the TPU rounds D to bfloat16
    err_pos = jnp.einsum("n,nft->ft", D, miss,
                         precision=jax.lax.Precision.HIGHEST).reshape(-1)
    errs = jnp.concatenate([err_pos, 1.0 - err_pos])
    idx = jnp.argmax(errs <= jnp.min(errs) + SELECT_TOL)
    f, t = jnp.unravel_index(idx % err_pos.shape[0], thresholds.shape)
    pol = jnp.where(idx < err_pos.shape[0], 1.0, -1.0)
    eps = errs[idx]
    return jnp.stack([f.astype(jnp.float32), thresholds[f, t], pol, eps]), eps


def client_mesh(devices: Sequence, axis: str = "clients") -> Mesh:
    """A 1-D mesh with one federated client per device.  The axis is
    Auto-typed: the step gathers from replicated tables with client-sharded
    indices, which an Explicit axis (``jax.make_mesh``'s default) refuses
    without a per-gather ``out_sharding``."""
    return Mesh(np.asarray(devices), (axis,), axis_types=(AxisType.Auto,))


def pack_clients(data: Dict, n_clients: int) -> Tuple[Array, ...]:
    """Stack a ``make_domain_data`` split into the step's inputs: client
    shards truncated to the smallest, ``(K, n, F)`` / ``(K, n)``, and the
    validation set cut into K equal slices ``(K, n_val, F)`` /
    ``(K, n_val)``."""
    K = n_clients
    n_local = min(c[0].shape[0] for c in data["clients"][:K])
    x = jnp.stack([c[0][:n_local] for c in data["clients"][:K]])
    y = jnp.stack([c[1][:n_local] for c in data["clients"][:K]])
    xv_full, yv_full = data["val"]
    nvl = xv_full.shape[0] // K
    xv = jnp.asarray(xv_full[:K * nvl]).reshape(K, nvl, -1)
    yv = jnp.asarray(yv_full[:K * nvl]).reshape(K, nvl)
    return x, y, xv, yv


def place(mesh: Mesh, client_axis: str, state: "FedMeshState",
          *data: Array) -> Tuple:
    """Put ``state`` and the client-sharded inputs on ``mesh``: leaves with
    a leading client axis are split over ``client_axis``, the ensemble and
    controller leaves are replicated."""
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                             state_shardings(mesh, client_axis),
                             is_leaf=lambda v: isinstance(v, P))
    dsh = NamedSharding(mesh, P(client_axis))
    return (jax.device_put(state, shardings),
            *(jax.device_put(a, dsh) for a in data))


def init_state(cfg: FedBoostConfig, n_clients: int, n_local: int,
               n_val_local: int, buffer_cap: int, ens_cap: int,
               key) -> FedMeshState:
    return FedMeshState(
        D=jnp.full((n_clients, n_local), 1.0 / n_local),
        buf_params=jnp.zeros((n_clients, buffer_cap, 4)),
        buf_stamp=jnp.zeros((n_clients, buffer_cap), jnp.int32),
        buf_count=jnp.zeros((n_clients,), jnp.int32),
        ens_params=jnp.zeros((ens_cap, 4)),
        ens_alpha=jnp.zeros((ens_cap,)),
        ens_count=jnp.zeros((), jnp.int32),
        val_margin=jnp.zeros((n_clients, n_val_local)),
        interval=jnp.asarray(scheduling._clipped_init(cfg.scheduler),
                             jnp.float32),
        prev_err=jnp.asarray(1.0, jnp.float32),
        counter=jnp.zeros((), jnp.int32),
        last_sync=jnp.zeros((), jnp.int32),
        sync_count=jnp.zeros((), jnp.int32),
        key=key,
    )


def make_fed_boost_step(cfg: FedBoostConfig, mesh, client_axis: str,
                        thresholds: Array):
    """Builds the compiled federated-boosting round.

    Returns step(state, x, y, xv, yv) -> state where x,y are (K, n, F)/(K, n)
    client shards and xv, yv the sharded validation slices.  All five are
    sharded over `client_axis` on dim 0.
    """
    sch = cfg.scheduler
    comp = cfg.compensation

    def local_round(state: FedMeshState, x, y, xv, yv) -> FedMeshState:
        """One boosting round on every client (no communication)."""

        def per_client(D, x, y):
            params, eps = _fit_stump_local(x, y, D, thresholds)
            margins = _predict_stumps(params[None], x)[0]
            a = adaboost_alpha(eps)
            w = D * jnp.exp(-a * y * margins)
            return params, eps, w / (jnp.sum(w) + 1e-30)

        params, eps, D = jax.vmap(per_client)(state.D, x, y)
        # append to ring buffer
        slot = state.buf_count % state.buf_params.shape[1]

        def append(bufp, bufs, p, s):
            return (bufp.at[s].set(p),
                    bufs.at[s].set(state.counter))

        bufp, bufs = jax.vmap(append)(state.buf_params, state.buf_stamp,
                                      params, slot)
        return state._replace(
            D=D, buf_params=bufp, buf_stamp=bufs,
            buf_count=state.buf_count + 1,
            counter=state.counter + 1)

    def sync(state: FedMeshState, x, y, xv, yv) -> FedMeshState:
        """Synchronization event: gather buffers, compensate, merge, update
        distributions and the adaptive interval."""
        cap = state.buf_params.shape[1]
        K = state.D.shape[0]

        def gather_merge(bufp, bufs, bufc, D, x, y, val_margin, xv, yv):
            # one client per shard along the client axis: strip the local
            # leading dim of 1 (n_clients must equal the axis size)
            bufp, bufs = bufp[0], bufs[0]     # bufc stays (1,): gathers to (K,)
            D, x, y, val_margin, xv, yv = (
                D[0], x[0], y[0], val_margin[0], xv[0], yv[0])
            # ---- collective: buffers cross the client axis here ----
            all_p = jax.lax.all_gather(bufp, client_axis, tiled=True)
            all_s = jax.lax.all_gather(bufs, client_axis, tiled=True)
            all_c = jax.lax.all_gather(bufc, client_axis, tiled=True)
            # (K*cap, 4) / (K*cap,) / (K,)
            flat_p = all_p.reshape(K * cap, 4)
            flat_s = all_s.reshape(K * cap)
            idx_in_buf = jnp.tile(jnp.arange(cap), K)
            valid = idx_in_buf < jnp.repeat(all_c, cap)
            # ownership: this client's own learners were already applied to
            # its local distribution at training time (full local alpha) —
            # skip them in the merged D update or they count twice
            owner = jnp.repeat(jnp.arange(K), cap)
            own = owner == jax.lax.axis_index(client_axis)

            # server-side alpha on the *global* validation distribution:
            # margins on local val slice, errors psum'd over clients
            mv = _predict_stumps(flat_p, xv)              # (M, n_val_local)
            yv_b = yv[None, :]
            local_miss = jnp.sum((jnp.where(mv > 0, 1.0, -1.0) != yv_b)
                                 .astype(jnp.float32), axis=1)
            local_n = jnp.asarray(yv.shape[0], jnp.float32)
            miss = jax.lax.psum(local_miss, client_axis)
            n_val = jax.lax.psum(local_n, client_axis)
            eps_srv = jnp.clip(miss / n_val, 0.02, 0.98)
            alpha = adaboost_alpha(eps_srv)
            tau = (state.counter - flat_s).astype(jnp.float32)
            alpha_t = jnp.where(
                valid, compensate(alpha, tau, comp), 0.0)     # (M,)

            # fold into replicated ensemble arrays
            base = state.ens_count
            pos = base + jnp.cumsum(valid.astype(jnp.int32)) - 1
            # invalid entries -> out-of-range sentinel, dropped by scatter
            pos = jnp.where(valid, pos, state.ens_params.shape[0])
            ens_p = state.ens_params.at[pos].set(flat_p, mode="drop")
            ens_a = state.ens_alpha.at[pos].set(alpha_t, mode="drop")
            n_new = jnp.sum(valid.astype(jnp.int32))

            # distribution update on local shard with the FOREIGN merged
            # learners (own ones already applied locally at training time)
            mx = _predict_stumps(flat_p, x)                # (M, n)
            upd = jnp.exp(-(alpha_t[:, None]) * y[None, :] * mx)
            use = valid & ~own
            D = D * jnp.prod(jnp.where(use[:, None], upd, 1.0), axis=0)
            D = D / (jnp.sum(D) + 1e-30)

            # update the running validation margin + global error
            val_margin = val_margin + jnp.sum(
                jnp.where(valid[:, None], alpha_t[:, None] * mv, 0.0), axis=0)
            vm_pred = jnp.where(val_margin > 0, 1.0, -1.0)
            loc_err = jnp.sum((vm_pred != yv).astype(jnp.float32))
            g_err = jax.lax.psum(loc_err, client_axis) / n_val
            return (ens_p, ens_a, n_new, D[None], val_margin[None], g_err)

        specs_in = (P(client_axis), P(client_axis), P(client_axis),
                    P(client_axis), P(client_axis), P(client_axis),
                    P(client_axis), P(client_axis), P(client_axis))
        specs_out = (P(), P(), P(), P(client_axis), P(client_axis), P())
        ens_p, ens_a, n_new, D, val_margin, g_err = jax.shard_map(
            gather_merge, mesh=mesh, in_specs=specs_in, out_specs=specs_out,
            check_vma=False)(
                state.buf_params, state.buf_stamp, state.buf_count,
                state.D, x, y, state.val_margin, xv, yv)

        # adaptive interval (eq. 1) on the new global error
        st = scheduling.SchedulerState(state.interval, state.prev_err,
                                       jnp.asarray(True))
        st = scheduling.adapt_interval(st, g_err, sch)

        return state._replace(
            D=D,
            buf_params=jnp.zeros_like(state.buf_params),
            buf_stamp=jnp.zeros_like(state.buf_stamp),
            buf_count=jnp.zeros_like(state.buf_count),
            ens_params=ens_p, ens_alpha=ens_a,
            ens_count=state.ens_count + n_new,
            val_margin=val_margin,
            interval=st.interval, prev_err=st.prev_error,
            last_sync=state.counter,
            sync_count=state.sync_count + 1)

    def step(state: FedMeshState, x, y, xv, yv) -> FedMeshState:
        state = local_round(state, x, y, xv, yv)
        due = (state.counter - state.last_sync) >= jnp.floor(state.interval
                                                             ).astype(jnp.int32)
        return jax.lax.cond(due, sync, lambda s, *a: s, state, x, y, xv, yv)

    return step


def publish_snapshot(state: FedMeshState, registry, tenant: str, *,
                     clock: float = 0.0):
    """Host-side publish() hook: snapshot the replicated ensemble arrays of
    a (possibly mid-training) :class:`FedMeshState` into a serving
    :class:`~repro.serve.registry.EnsembleRegistry` — or into a sharded
    :class:`~repro.serve.shard.ShardCluster`, whose ``publish_packed``
    routes the snapshot to the tenant's rendezvous-owning shard so that
    host's subscribers (cache invalidation, gossip digest) see the new
    version before any anti-entropy round runs.

    ``ens_params`` is already the packed ``(T, 4)`` stump wire format, so
    this is a device_get + slice — the compiled train step never blocks on
    serving, and readers only ever see the frozen snapshot."""
    n = int(jax.device_get(state.ens_count))
    params = jnp.asarray(jax.device_get(state.ens_params)[:n])
    alphas = jnp.asarray(jax.device_get(state.ens_alpha)[:n])
    with obs.span("train.publish", sim_t=clock, tenant=tenant,
                  n_learners=n) as sp:
        snap = registry.publish_packed(
            tenant, params, alphas, clock=float(clock),
            train_progress=int(jax.device_get(state.counter)))
        sp.set(version=getattr(snap, "version", None))
        sp.end_sim(clock)
    obs.count("train.publishes")
    return snap


def state_shardings(mesh, client_axis: str) -> FedMeshState:
    """PartitionSpecs for FedMeshState (client-axis leaves sharded)."""
    c = P(client_axis)
    r = P()
    return FedMeshState(
        D=c, buf_params=c, buf_stamp=c, buf_count=c,
        ens_params=r, ens_alpha=r, ens_count=r,
        val_margin=c, interval=r, prev_err=r, counter=r,
        last_sync=r, sync_count=r, key=r)
