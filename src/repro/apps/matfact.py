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

from ..core.ps import PSApp
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

    def loss(x, locals_):
        # ratings come in as arguments (not closed-over constants), so a
        # large rating set is not baked into the compiled program
        L, R = unpack(x)
        if "dc" in locals_:
            # dense form: the counted ratings on the [n, m_pad] grid
            rows = (-1, locals_["dc"].shape[-1])
            sse = ops.mf_sse(L, R, locals_["dv"].reshape(rows),
                             locals_["dc"].reshape(rows))
            return sse / locals_["vv"].size
        all_i, all_j = locals_["ii"].ravel(), locals_["jj"].ravel()
        pred = jnp.sum(L[all_i] * R[:, all_j].T, axis=-1)
        return jnp.mean(jnp.square(locals_["vv"].ravel() - pred))

    local0 = {"ii": ii, "jj": jj, "vv": vv}
    if ops.get_backend() != "ref" and mf_sse.supported(n, _lanes(m)):
        # Each worker's ratings on its rows of the grid, for the loss only
        # (worker_update samples ii/jj/vv): dc counts each observed pair
        # (duplicates keep their weight), dv is D there and 0 elsewhere, so
        # unobserved truth never enters a worker's data.  The grid holds
        # n·m_pad·5 bytes whatever the density.
        m_pad = _lanes(m)
        w = jnp.broadcast_to(jnp.arange(P)[:, None], ii.shape)
        dc = jnp.zeros((P, rows_per, m_pad), jnp.int8).at[
            w, ii - w * rows_per, jj].add(jnp.int8(1))
        D_w = jnp.pad(D, ((0, 0), (0, m_pad - m))).reshape(P, rows_per, m_pad)
        local0.update(dv=jnp.where(dc > 0, D_w, 0.0), dc=dc)
    return PSApp(name="matfact", dim=(n + m) * k, n_workers=P,
                 x0=_pack(L0, R0), local0=local0,
                 worker_update=worker_update, loss=loss)


def sequential_baseline(cfg: MFConfig, n_clocks: int):
    """Single-worker (strongly consistent) reference: same app with P=1
    doing P*batch ratings per clock.  Used as the gold standard in tests."""
    import dataclasses
    c1 = dataclasses.replace(cfg, n_workers=1, batch=cfg.batch * cfg.n_workers)
    app = make_mf_app(c1)
    from ..core.consistency import bsp
    from ..core.ps import simulate
    return simulate(app, bsp(), n_clocks)
