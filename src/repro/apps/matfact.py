"""Low-rank matrix factorization via SGD on the parameter server.

This is the paper's primary SGD benchmark (Netflix, rank 100).  We scale it
down to laptop size but keep the *exact* update equations of the paper:

    L_i*  <- L_i* + γ (e_ij R_*j^T − λ L_i*)
    R_*j  <- R_*j + γ (e_ij L_i*^T − λ R_*j)      e_ij = D_ij − L_i* R_*j

Both factor matrices live on the PS (packed into the flat vector); the
observed ratings are partitioned by rows across workers — data parallelism —
exactly as described in the paper.  Each clock a worker processes a
fixed-size minibatch of its own ratings and INCs the resulting additive
deltas.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..core.ps import PSApp, fold_workers
from ..core.timemodel import TimeModel
from ..kernels import mf_sse, ops


def mf_time_model(**kw) -> TimeModel:
    """Paper-class wall-clock constants for the MF/SGD app.

    The 1 GbE defaults of `TimeModel` already describe the paper's MF
    cluster (50 ms SGD clocks, ~4 MB of factor rows per producer); this is
    the single place benchmarks get them from, so the Fig 2 time axis and
    the auto-tuner stay on the same constants.
    """
    return TimeModel(**kw)


@dataclass(frozen=True)
class MFConfig:
    n_rows: int = 240
    n_cols: int = 240
    rank: int = 24           # K — lifted from 12 once the ring-view kernel
                             # streamed d-blocks (ROADMAP d-scaling): the
                             # benchmarks are view-bound, not compile-bound,
                             # so doubling d costs ~linear sim time (see the
                             # d-scaling profile in benchmarks/sweep_bench.py)
    true_rank: int = 12
    density: float = 0.18    # fraction of observed entries
    noise: float = 0.01
    n_workers: int = 8
    batch: int = 128         # ratings per worker per clock
    lr: float = 0.7          # γ (absorbs constants, as in the paper; chosen
                             # "large while still converging with staleness 0")
    lr_decay: bool = True    # γ_t = γ / sqrt(1 + t)
    lam: float = 1e-4        # λ
    init_scale: float = 0.1
    seed: int = 0


def _pack(L, R):
    return jnp.concatenate([L.ravel(), R.ravel()])


def _lanes(m: int) -> int:
    return -(-m // 128) * 128


def make_mf_app(cfg: MFConfig) -> PSApp:
    """MF's app on synthetic data drawn from ``cfg.seed``: a rank
    ``true_rank`` truth plus noise, each worker's ratings drawn from its
    own block of rows (`mf_app`)."""
    n, m, k, P = cfg.n_rows, cfg.n_cols, cfg.rank, cfg.n_workers
    rng = jax.random.PRNGKey(cfg.seed)
    k_t, k_o, k_n, k_i = jax.random.split(rng, 4)

    # Synthetic ground truth and observations.
    kL, kR = jax.random.split(k_t)
    Lstar = jax.random.normal(kL, (n, cfg.true_rank)) / jnp.sqrt(cfg.true_rank)
    Rstar = jax.random.normal(kR, (cfg.true_rank, m)) / jnp.sqrt(cfg.true_rank)
    D = (jnp.matmul(Lstar, Rstar, precision=jax.lax.Precision.HIGHEST)
         + cfg.noise * jax.random.normal(k_n, (n, m)))

    # Observed entries, partitioned by row blocks across workers (the paper
    # partitions data across machines; row blocks keep L-updates local-ish
    # while R rows are contended — the interesting PS case).
    assert n % P == 0, "n_rows must divide by n_workers"
    rows_per = n // P
    n_obs_per = int(rows_per * m * cfg.density)
    keys = jax.random.split(k_o, P)

    def sample_worker(key, w):
        ki, kj = jax.random.split(key)
        ii = jax.random.randint(ki, (n_obs_per,), 0, rows_per) + w * rows_per
        jj = jax.random.randint(kj, (n_obs_per,), 0, m)
        return ii.astype(jnp.int32), jj.astype(jnp.int32)

    ii, jj = jax.vmap(sample_worker)(keys, jnp.arange(P))
    vv = D[ii, jj]                                       # [P, n_obs_per]

    kLi, kRi = jax.random.split(k_i)
    L0 = cfg.init_scale * jax.random.normal(kLi, (n, k))
    R0 = cfg.init_scale * jax.random.normal(kRi, (k, m))
    return mf_app(cfg, _pack(L0, R0), ii, jj, vv)


def mf_app(cfg: MFConfig, x0, ii, jj, vv) -> PSApp:
    """MF's app on given data: the factors ``x0 [(n + m) k]`` and each
    worker's ratings ``ii``, ``jj``, ``vv [P, n_obs]`` (worker ``p``'s
    rows lie in its block ``[p n/P, (p+1) n/P)``; a (row, column) pair
    drawn twice holds one rating, since the dense grid below keeps one
    value per pair).  ``cfg`` gives the shapes and the update's knobs; its
    data knobs are not read.

    On a Pallas backend, where the kernel takes the shape, ``local0``
    gains each worker's ratings on its rows of the dense grid, built from
    that worker's ratings alone, one worker after another.  A caller may
    hand in the ratings of a block of workers only (``ii`` etc. ``[Pl,
    n_obs]``), as a ``shard_map`` over the worker axes does: each shard
    then builds its own workers' grids where their ratings are."""
    n, m, k, P = cfg.n_rows, cfg.n_cols, cfg.rank, cfg.n_workers
    assert n % P == 0, "n_rows must divide by n_workers"
    rows_per = n // P
    n_obs_per = ii.shape[1]
    m_pad = _lanes(m)

    def unpack(x):
        return x[: n * k].reshape(n, k), x[n * k:].reshape(k, m)

    def worker_update(view, local, _wid, clock, rng):
        L, R = unpack(view)
        gamma = cfg.lr / jnp.sqrt(1.0 + clock) if cfg.lr_decay else cfg.lr
        idx = jax.random.randint(rng, (cfg.batch,), 0, n_obs_per)
        i, j, v = local["ii"][idx], local["jj"][idx], local["vv"][idx]
        Li = L[i]                      # [B, k]
        Rj = R[:, j].T                 # [B, k]
        e = v - jnp.sum(Li * Rj, axis=-1)
        dL = jnp.zeros_like(L).at[i].add(gamma * (e[:, None] * Rj - cfg.lam * Li))
        dR = jnp.zeros_like(R).at[:, j].add(
            (gamma * (e[:, None] * Li - cfg.lam * Rj)).T)
        return _pack(dL, dR), local

    def loss_sum(x, locals_, worker_ids):
        # ratings come in as arguments (not closed-over constants), so a
        # large rating set is not baked into the compiled program
        L, R = unpack(x)
        ii_b, jj_b, vv_b = locals_["ii"], locals_["jj"], locals_["vv"]
        if "dc" in locals_:
            # dense form: each worker's counted ratings on its rows of the
            # grid, against its rows of L (one kernel, a grid step per
            # worker block)
            Lw = jax.lax.dynamic_slice_in_dim(
                L.reshape(P, rows_per, k), worker_ids[0], ii_b.shape[0])
            sse = jax.vmap(lambda Lp, V, C: ops.mf_sse(Lp, R, V, C))(
                Lw, locals_["dv"], locals_["dc"])
            return sse, n_obs_per
        pred = jnp.sum(L[ii_b.ravel()] * R[:, jj_b.ravel()].T, axis=-1)
        err = vv_b - pred.reshape(vv_b.shape)
        return jnp.sum(jnp.square(err), axis=-1), n_obs_per

    def loss(x, locals_):
        sse, count = loss_sum(x, locals_, jnp.arange(P, dtype=jnp.int32))
        return fold_workers(sse) / (count * P)

    local0 = {"ii": ii, "jj": jj, "vv": vv}
    if ops.get_backend() != "ref" and mf_sse.supported(rows_per, m_pad):
        # Each worker's ratings on its rows of the grid, for the loss only
        # (worker_update samples ii/jj/vv): dc counts each observed pair
        # (duplicates keep their weight), dv holds the rating there and 0
        # elsewhere, so unobserved truth never enters a worker's data.
        # The grid holds n·m_pad·5 bytes whatever the density.
        def grid(ratings):
            i, j, v = ratings
            r = i % rows_per               # the row within the worker's block
            dc = jnp.zeros((rows_per, m_pad), jnp.int8).at[r, j].add(
                jnp.int8(1))
            return jnp.zeros((rows_per, m_pad), vv.dtype).at[r, j].set(v), dc

        dv, dc = jax.lax.map(grid, (ii, jj, vv))
        local0.update(dv=dv, dc=dc)
    return PSApp(name="matfact", dim=(n + m) * k, n_workers=P,
                 x0=x0, local0=local0, worker_update=worker_update,
                 loss=loss, loss_sum=loss_sum)


def sequential_baseline(cfg: MFConfig, n_clocks: int):
    """Single-worker (strongly consistent) reference: same app with P=1
    doing P*batch ratings per clock.  Used as the gold standard in tests."""
    import dataclasses
    c1 = dataclasses.replace(cfg, n_workers=1, batch=cfg.batch * cfg.n_workers)
    app = make_mf_app(c1)
    from ..core.consistency import bsp
    from ..core.ps import simulate
    return simulate(app, bsp(), n_clocks)
