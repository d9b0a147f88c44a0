"""Matrix factorisation on the PS: the program's app, built from a
configuration file, and its plain reference.

The ratings and the initial factors are the job's dataset: both sides make
them from the configuration's ``data_seed``.  The run's seed draws the
job's random stream (each worker's minibatches, the deliveries), so runs
with different seeds do the same work in another order.

The reference re-implements the semantics in plain ``jax.numpy`` and
imports nothing of the program: the data generator (the same key stream
from ``data_seed``), the paper's SGD update for MF (Dai et al., AAAI
2015), the ring of in-transit updates with per-channel visibility clocks,
the BSP and ESSP read rules, the eager delivery draws, and the mean
squared error over all ratings.  It runs one clock after another with no
kernel, no sharding and no runtime, and computes the loss one worker's
ratings at a time so that it fits beside nothing else on the chip.

``dtype`` puts the reference in a lower precision (the control), and
``fault`` plants one of the faults a broken timed path could have, so that
the comparison can be shown to fail on each.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import work

MF_KEYS = ("n_rows", "n_cols", "rank", "true_rank", "density", "noise",
           "n_workers", "batch", "lr", "lr_decay", "lam", "init_scale")
RING_INVALID = -(10 ** 8)    # a ring slot whose clock is below this is empty
RING_EMPTY = -(10 ** 9)
HIGHEST = jax.lax.Precision.HIGHEST
FAULTS = ("half_batch", "doubled_answer")


def dims(config: dict) -> dict:
    n, m, k, P = (config[key] for key in ("n_rows", "n_cols", "rank",
                                          "n_workers"))
    n_obs = int((n // P) * m * config["density"])
    return dict(n=n, m=m, k=k, P=P, B=config["batch"], n_obs=n_obs,
                N=P * n_obs, d=(n + m) * k)


def leaves(config: dict) -> dict:
    """The model's parameter leaves as slices of the flat table."""
    g = dims(config)
    return {"L": (0, g["n"] * g["k"]), "R": (g["n"] * g["k"], g["d"])}


def seed32(seed: int) -> np.uint32:
    """``jax.random.PRNGKey`` keeps the low 32 bits of a seed; so do we."""
    return np.uint32(seed % 2 ** 32)


# --------------------------------------------------------------------------
# the system under test
# --------------------------------------------------------------------------
def build_app(config: dict):
    """The program's MF app, its data and initial factors made on the device
    in one jitted call from ``data_seed``."""
    from repro.apps.matfact import MFConfig, make_mf_app
    made = {}

    def gen(s):
        app = make_mf_app(MFConfig(**{k: config[k] for k in MF_KEYS},
                                   seed=s))
        made["app"] = app
        return app.x0, app.local0

    x0, local0 = jax.jit(gen)(seed32(config["data_seed"]))
    return dataclasses.replace(made["app"], x0=x0, local0=local0)


def work_counts(config: dict, traffic: dict, mesh_shape: dict) -> dict:
    """Required work: per kernel call on one chip, and per clock for the
    whole job."""
    g = dims(config)
    W, data, model = traffic["window"], mesh_shape["data"], mesh_shape["model"]
    d_chip = g["d"] / model
    return {
        "ring_view": work.ring_view(W, g["P"], g["P"] // data, d_chip),
        "vap_suffix_norms": work.vap_suffix_norms(W, g["P"], d_chip),
        "clock": work.mf_clock(g["n"], g["m"], g["k"], g["P"], g["B"],
                               g["N"], W),
        "samples_per_clock": g["P"] * g["B"],
    }


# --------------------------------------------------------------------------
# the plain reference
# --------------------------------------------------------------------------
def make_data(config: dict):
    """Ratings and initial factors from ``data_seed``: ``(x0 [d], ii, jj, vv)``
    with ``[P, n_obs]`` ratings, worker ``p`` holding rows of its block."""
    g = dims(config)
    n, m, k, P, n_obs = g["n"], g["m"], g["k"], g["P"], g["n_obs"]
    tr, rows = config["true_rank"], n // P

    def gen(s):
        k_t, k_o, k_n, k_i = jax.random.split(jax.random.PRNGKey(s), 4)
        kL, kR = jax.random.split(k_t)
        Ls = jax.random.normal(kL, (n, tr)) / jnp.sqrt(tr)
        Rs = jax.random.normal(kR, (tr, m)) / jnp.sqrt(tr)
        D = (jnp.matmul(Ls, Rs, precision=HIGHEST)
             + config["noise"] * jax.random.normal(k_n, (n, m)))
        ii, jj = [], []
        for w, key in enumerate(jax.random.split(k_o, P)):
            ki, kj = jax.random.split(key)
            ii.append(jax.random.randint(ki, (n_obs,), 0, rows) + w * rows)
            jj.append(jax.random.randint(kj, (n_obs,), 0, m))
        ii = jnp.stack(ii).astype(jnp.int32)
        jj = jnp.stack(jj).astype(jnp.int32)
        kLi, kRi = jax.random.split(k_i)
        L0 = config["init_scale"] * jax.random.normal(kLi, (n, k))
        R0 = config["init_scale"] * jax.random.normal(kRi, (k, m))
        return jnp.concatenate([L0.ravel(), R0.ravel()]), ii, jj, D[ii, jj]

    return jax.jit(gen)(seed32(config["data_seed"]))


def _reference_run(config, traffic, n_clocks, dtype, fault):
    g = dims(config)
    n, m, k, P, n_obs = g["n"], g["m"], g["k"], g["P"], g["n_obs"]
    B = g["B"] // 2 if fault == "half_batch" else g["B"]
    W, model, s = traffic["window"], traffic["model"], traffic["staleness"]
    if model not in ("bsp", "essp"):
        raise ValueError(f"no reference for consistency {model!r}")
    lr, lam = config["lr"], config["lam"]
    bounds = list(leaves(config).values())
    producers = jnp.arange(P)
    f32 = jnp.float32

    def unpack(x):
        return x[:n * k].reshape(n, k), x[n * k:].reshape(k, m)

    def loss(x, ii, jj, vv):
        L, R = unpack(x)

        def one_worker(rows):
            i, j, v = rows
            pred = jnp.sum(L[i] * R[:, j].T, axis=-1)
            return jnp.sum(jnp.square(v - pred), dtype=f32)

        return jnp.sum(jax.lax.map(one_worker, (ii, jj, vv))) / (P * n_obs)

    def update(view, i_all, j_all, v_all, c, key):
        L, R = unpack(view)
        gamma = (lr / jnp.sqrt(1.0 + c) if config["lr_decay"]
                 else jnp.float32(lr)).astype(dtype)
        idx = jax.random.randint(key, (B,), 0, n_obs)
        i, j, v = i_all[idx], j_all[idx], v_all[idx]
        Li, Rj = L[i], R[:, j].T
        e = v - jnp.sum(Li * Rj, axis=-1)
        dL = jnp.zeros_like(L).at[i].add(gamma * (e[:, None] * Rj - lam * Li))
        dR = jnp.zeros_like(R).at[:, j].add(
            (gamma * (e[:, None] * Li - lam * Rj)).T)
        return jnp.concatenate([dL.ravel(), dR.ravel()])

    def leaf_l2(u):
        """``[..., d]`` -> ``[..., leaves]``: the norm over each leaf."""
        return jnp.stack([jnp.linalg.norm(u[..., a:b], axis=-1)
                          for a, b in bounds], axis=-1)

    def suffix_norms(uring, uclock, c):
        out, suffix = [jnp.zeros((P,), f32)], jnp.zeros((P, g["d"]), dtype)
        for kk in range(1, W + 1):
            hit = (uclock == c - kk).astype(dtype)
            suffix = suffix + jnp.einsum("w,wqd->qd", hit, uring,
                                         precision=HIGHEST)
            out.append(jnp.max(jnp.abs(suffix), axis=-1).astype(f32))
        return jnp.stack(out)                                # [W+1, P]

    def table(base, uring, uclock):
        valid = (uclock > RING_INVALID).astype(dtype)
        return base + jnp.einsum("w,wqd->d", valid, uring, precision=HIGHEST)

    def run(x0, ii, jj, vv, seed):
        x0, vv = x0.astype(dtype), vv.astype(dtype)

        def step(carry, c):
            base, uring, uclock, cview, rng = carry
            rng, k_upd, k_net = jax.random.split(rng, 3)
            norms = suffix_norms(uring, uclock, c)
            if model == "bsp":
                forced = cview < c - 1
                cview = jnp.full_like(cview, c - 1)
            else:
                forced = cview < c - s - 1
                cview = jnp.where(forced, c - 1, cview)
            # read-my-writes: a worker always sees its own last update
            cview = jnp.where(jnp.eye(P, dtype=bool), c - 1, cview)
            staleness = cview - c
            kcur = jnp.clip(c - 1 - cview, 0, W)
            intransit = jnp.max(norms[kcur, producers[None, :]])

            valid = uclock > RING_INVALID
            vis = ((uclock[None, :, None] <= cview[:, None, :])
                   & valid[None, :, None])
            views = base + jnp.einsum(
                "rwq,wqd->rd", vis.astype(dtype), uring, precision=HIGHEST)
            u = jax.vmap(update, in_axes=(0, 0, 0, 0, None, 0))(
                views, ii, jj, vv, c, jax.random.split(k_upd, P))
            if fault == "doubled_answer":
                u = u.at[0].multiply(2)

            slot = c % W
            old = jnp.where(uclock[slot] > RING_INVALID, 1.0, 0.0).astype(
                dtype)
            base = base + old * jnp.sum(uring[slot], axis=0)
            uring = uring.at[slot].set(u)
            uclock = uclock.at[slot].set(c)

            if model == "bsp":
                delivered = jnp.ones((P, P), bool)
                cview = jnp.full_like(cview, c)
            else:
                k1, k2 = jax.random.split(k_net)
                pushed = jax.random.uniform(k1, (P, P)) < traffic["push_prob"]
                congested = jax.random.bernoulli(
                    k2, traffic["straggler_prob"], (P, P))
                delivered = pushed & ~congested
                cview = jnp.where(delivered, c, cview)

            x = table(base, uring, uclock)
            u32 = u.astype(f32)
            out = dict(loss_ref=loss(x, ii, jj, vv),
                       loss_view=loss(views[0], ii, jj, vv),
                       staleness=staleness, forced=forced,
                       delivered=delivered,
                       intransit_inf=intransit,
                       u_leaf=leaf_l2(u32),
                       g_leaf=leaf_l2(jnp.sum(u32, axis=0)))
            return (base, uring, uclock, cview, rng), out

        carry = (x0,
                 jnp.zeros((W, P, g["d"]), dtype),
                 jnp.full((W,), RING_EMPTY, jnp.int32),
                 jnp.full((P, P), -1, jnp.int32),
                 jax.random.PRNGKey(seed))
        carry, ys = jax.lax.scan(step, carry,
                                 jnp.arange(n_clocks, dtype=jnp.int32))
        x_end = table(*carry[:3]).astype(f32)
        return ys, x_end, x0.astype(f32)

    return jax.jit(run)


def ring_clocks(traffic: dict) -> range:
    """The clocks whose updates the ring holds after the job's first
    segment."""
    K, W = traffic["segment_clocks"], traffic["window"]
    return range(max(0, K - W), K)


def reference(config: dict, traffic: dict, seed: int, n_clocks: int,
              dtype=jnp.float32, fault: str | None = None) -> dict:
    """Readings of the first ``n_clocks`` clocks of the job, computed
    plainly; the same keys as the harness's readings of the program."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; expected one of {FAULTS}")
    x0, ii, jj, vv = make_data(config)
    run = _reference_run(config, traffic, n_clocks, dtype, fault)
    ys, x_end, x_start = run(x0, ii, jj, vv, seed32(seed))
    del x0, ii, jj, vv
    out = {key: np.asarray(v) for key, v in ys.items()
           if key not in ("u_leaf", "g_leaf")}
    u_leaf = np.asarray(ys["u_leaf"])
    out["ring"] = {c: u_leaf[c] for c in ring_clocks(traffic)}
    out["change"] = leaf_norms(config, x_end - x_start)
    out["grad_leaf"] = dict(zip(leaves(config),
                                np.asarray(ys["g_leaf"][0]).tolist()))
    return out


def leaf_norms(config: dict, x) -> dict:
    return {name: float(jnp.linalg.norm(x[a:b]))
            for name, (a, b) in leaves(config).items()}


def ring_leaf_norms(config: dict, uring, uclock) -> dict:
    """From the program's state: the clocks of ``ring_clocks`` that the ring
    holds, each mapped to the norm of every producer's update over each
    leaf, ``[P, leaves]``."""
    bounds = list(leaves(config).values())

    @jax.jit
    def norms(uring):
        return jnp.stack([jnp.linalg.norm(uring[..., a:b], axis=-1)
                          for a, b in bounds], axis=-1)

    clocks = np.asarray(uclock)
    held = np.asarray(norms(uring))                     # [W, P, leaves]
    return {int(c): held[w] for w, c in enumerate(clocks)}
