"""Matrix factorisation on the PS at a size one chip does not hold: the
program's app with its data made on the cell's mesh, and its plain
reference.

The job is `chipbench.apps.matfact`'s: the same update, traffic, readings
and reference run (imported from there).  Only the data are made
otherwise.  That module draws a dense ``[n, m]`` field of ratings on one
device and reads the observed entries from it; at this size the field
alone takes 9.3 GB.  Here each worker draws its own observations from its
key: the rows of its block and the columns, then each rating as the truth
at that entry plus noise drawn for that entry,

    v[i, j] = <L*[:, i], R*[:, j]> + noise · normal(k_n, i m + j),

so a pair drawn twice holds one rating, with the rank-``true_rank`` truth,
``k_n`` and the initial factors drawn from ``data_seed``.  `build_app`
makes them on the mesh, each data shard's workers on its own chips, and
hands them to the program's MF app (`repro.apps.matfact.mf_app`), which
builds each worker's grid of the objective from that worker's ratings
there.  `make_data` makes the same
arrays with plain ``jax.numpy`` on one device, for the reference.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.apps.matfact import (FAULTS, MF_KEYS, _reference_run,  # noqa: F401
                                    dims, leaf_norms, leaves, ring_clocks,
                                    ring_leaf_norms, seed32, work_counts)


def _entry_normal(key, entry):
    """A standard normal draw per entry index, a function of ``key`` and
    the index alone: a pair drawn twice holds one rating, as in a ratings
    matrix (the objective's grid holds one value per pair).  Threefry of
    the pair (index, 0) gives the bits, element by element (a key per
    element would be ``[N, 2]``, padded to 128 lanes on the chip)."""
    from jax.extend.random import threefry_2x32
    bits = threefry_2x32(key, jnp.concatenate([entry, jnp.zeros_like(entry)]))
    return _normal_from_bits(bits[:entry.size])


def _normal_from_bits(bits):
    """A standard normal from 32 random bits: the top 23 as a uniform half
    a step inside (0, 1), exact in float32 (so never 0 or 1, where the
    inverse error function is infinite), through the inverse error
    function."""
    u = ((bits >> 9).astype(jnp.float32) + 0.5) * 2.0 ** -23
    return np.sqrt(2.0).astype(np.float32) * jax.lax.erf_inv(2.0 * u - 1.0)


def _generator(config: dict):
    """``(seed, shard=0, shards=1) -> (x0 [d], ii, jj, vv [P/shards,
    n_obs])``: the factors, and the ratings of the ``shard``-th block of
    ``P/shards`` workers."""
    g = dims(config)
    n, m, k, P, n_obs = g["n"], g["m"], g["k"], g["P"], g["n_obs"]
    tr, rows = config["true_rank"], n // P

    def worker(key, w, Ls, Rs, k_n):
        ki, kj = jax.random.split(key)
        ii = (jax.random.randint(ki, (n_obs,), 0, rows) + w * rows).astype(
            jnp.int32)
        jj = jax.random.randint(kj, (n_obs,), 0, m).astype(jnp.int32)
        # the truth's inner product one rank component at a time: a
        # gather of tr floats per rating would be laid out [n_obs, tr],
        # padded from tr to 128 lanes on the chip
        truth = Ls[0][ii] * Rs[0][jj]
        for t in range(1, tr):
            truth = truth + Ls[t][ii] * Rs[t][jj]
        vv = truth + config["noise"] * _entry_normal(
            k_n, ii.astype(jnp.uint32) * m + jj.astype(jnp.uint32))
        return ii, jj, vv

    def gen(s, shard=0, shards=1):
        k_t, k_o, k_n, k_i = jax.random.split(jax.random.PRNGKey(s), 4)
        kL, kR = jax.random.split(k_t)
        Ls = jax.random.normal(kL, (tr, n)) / jnp.sqrt(tr)
        Rs = jax.random.normal(kR, (tr, m)) / jnp.sqrt(tr)
        Pl = P // shards
        keys = jax.lax.dynamic_slice_in_dim(jax.random.split(k_o, P),
                                            shard * Pl, Pl)
        ii, jj, vv = jax.vmap(worker, in_axes=(0, 0, None, None, None))(
            keys, shard * Pl + jnp.arange(Pl), Ls, Rs, k_n)
        kLi, kRi = jax.random.split(k_i)
        L0 = config["init_scale"] * jax.random.normal(kLi, (n, k))
        R0 = config["init_scale"] * jax.random.normal(kRi, (k, m))
        return jnp.concatenate([L0.ravel(), R0.ravel()]), ii, jj, vv

    return gen


# --------------------------------------------------------------------------
# the system under test
# --------------------------------------------------------------------------
def build_app(config: dict):
    """The program's MF app, its data and initial factors made in one
    jitted call from ``data_seed`` on the cell's mesh (the first chips, as
    the harness takes them): inside a ``shard_map``, each chip makes its
    data shard's ratings and their grids of the objective, so the ratings
    and grids come out sharded over ``"data"``, the factors on every
    chip."""
    from jax.sharding import PartitionSpec as P_

    from repro.apps.matfact import MFConfig, mf_app
    from repro.launch.mesh import make_ps_mesh

    shape = config["mesh"]
    mesh = make_ps_mesh(devices=jax.devices()[:shape["data"] * shape["model"]],
                        **shape)
    cfg = MFConfig(**{key: config[key] for key in MF_KEYS})
    gen = _generator(config)
    made = {}

    def shard(s):
        app = mf_app(cfg, *gen(s, jax.lax.axis_index("data"), shape["data"]))
        made["app"] = app
        return app.x0, app.local0

    make = jax.shard_map(shard, mesh=mesh, in_specs=P_(),
                         out_specs=(P_(), P_("data")), check_vma=False)
    x0, local0 = jax.jit(make)(seed32(config["data_seed"]))
    return dataclasses.replace(made["app"], x0=x0, local0=local0)


# --------------------------------------------------------------------------
# the plain reference
# --------------------------------------------------------------------------
def make_data(config: dict):
    """``(x0 [d], ii, jj, vv)`` from ``data_seed`` on one device, with
    ``[P, n_obs]`` ratings, worker ``p`` holding rows of its block."""
    return jax.jit(_generator(config))(seed32(config["data_seed"]))


def reference(config: dict, traffic: dict, seed: int, n_clocks: int,
              dtype=jnp.float32, fault: str | None = None) -> dict:
    """`chipbench.apps.matfact.reference`'s readings of the job's first
    ``n_clocks`` clocks, on this module's data."""
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
