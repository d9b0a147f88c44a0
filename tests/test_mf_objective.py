"""MF's full-data objective: the dense-block form on a Pallas backend, the
per-rating gather form on the reference backend, and the runtime keeping
the dense leaves out of its carry."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P_

from repro.apps.matfact import MFConfig, make_mf_app
from repro.core import essp
from repro.kernels import ops
from repro.psrun import PSRuntime, default_mesh

CFG = MFConfig(n_rows=64, n_cols=200, rank=24, n_workers=4, batch=16)


def _app(backend: str):
    ops.set_backend(backend)
    try:
        return make_mf_app(CFG)
    finally:
        ops.set_backend("auto")


def _gather_loss(x, local):
    """The objective as the parent commit wrote it."""
    n, m, k = CFG.n_rows, CFG.n_cols, CFG.rank
    L, R = x[: n * k].reshape(n, k), x[n * k:].reshape(k, m)
    all_i, all_j = local["ii"].ravel(), local["jj"].ravel()
    pred = jnp.sum(L[all_i] * R[:, all_j].T, axis=-1)
    return jnp.mean(jnp.square(local["vv"].ravel() - pred))


def _gather_loss_by_worker(x, local):
    """The same objective summed worker by worker, the sums added in
    worker order: the order of additions the apps use on every mesh."""
    n, m, k = CFG.n_rows, CFG.n_cols, CFG.rank
    L, R = x[: n * k].reshape(n, k), x[n * k:].reshape(k, m)
    pred = jnp.sum(L[local["ii"].ravel()] * R[:, local["jj"].ravel()].T,
                   axis=-1).reshape(local["vv"].shape)
    sums = jnp.sum(jnp.square(local["vv"] - pred), axis=-1)
    total = sums[0]
    for p in range(1, sums.shape[0]):
        total = total + sums[p]
    return total / local["vv"].size


def _points(app):
    step = 0.05 * jax.random.normal(jax.random.PRNGKey(3), app.x0.shape)
    return [app.x0, app.x0 + step]


def test_dense_leaves_on_a_pallas_backend():
    app = _app("pallas_interpret")
    local = app.local0
    P, rows = CFG.n_workers, CFG.n_rows // CFG.n_workers
    assert sorted(local) == ["dc", "dv", "ii", "jj", "vv"]
    assert local["dc"].shape == local["dv"].shape == (P, rows, 256)
    assert local["dc"].dtype == jnp.int8
    dc, dv = np.asarray(local["dc"]), np.asarray(local["dv"])
    assert dc.sum() == local["vv"].size
    assert not dv[dc == 0].any()
    w = np.arange(P)[:, None]
    ii, jj = np.asarray(local["ii"]), np.asarray(local["jj"])
    np.testing.assert_array_equal(dv[w, ii - w * rows, jj],
                                  np.asarray(local["vv"]))
    ops.set_backend("pallas_interpret")
    try:
        for x in _points(app):
            np.testing.assert_allclose(float(app.loss(x, local)),
                                       float(_gather_loss(x, local)),
                                       rtol=1e-6)
    finally:
        ops.set_backend("auto")


def test_reference_backend_keeps_the_gather_form():
    app = _app("ref")
    assert sorted(app.local0) == ["ii", "jj", "vv"]
    for x in _points(app):
        got = jax.jit(app.loss)(x, app.local0)
        want = jax.jit(_gather_loss_by_worker)(x, app.local0)
        assert np.asarray(got).tobytes() == np.asarray(want).tobytes()
        np.testing.assert_allclose(float(got), float(_gather_loss(
            x, app.local0)), rtol=1e-6)


def test_runtime_returns_fixed_locals_uncopied():
    """MF's update hands its ratings back unchanged, so a segment returns
    the arrays it was given (no copy of the dense leaves), placed with the
    program's input sharding, with the same trace as a run that restarts
    from the returned state."""
    app = _app("pallas_interpret")
    ops.set_backend("pallas_interpret")
    try:
        fn_mesh = default_mesh(CFG.n_workers)
        fn = PSRuntime(fn_mesh).run_fn(app, essp(2), 2)
        state = fn.init_state(5)
        trace, new = fn.run_from(state)
        again, _ = fn.run_from(new)
        whole = PSRuntime(default_mesh(CFG.n_workers)).run(app, essp(2), 4,
                                                           seed=5)
    finally:
        ops.set_backend("auto")
    for key in app.local0:
        assert new.local[key] is state.local[key]
        assert trace.locals_final[key] is state.local[key]
        assert state.local[key].sharding == NamedSharding(fn_mesh, P_("data"))
    np.testing.assert_array_equal(
        np.concatenate([trace.loss_ref, again.loss_ref]), whole.loss_ref)


def _gathered_shapes(jaxpr, in_scan=False, found=None):
    """Operand shapes of the ``all_gather``s in ``jaxpr``, split by whether
    they sit inside a ``scan`` body."""
    found = {True: [], False: []} if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "all_gather":
            found[in_scan] += [v.aval.shape for v in eqn.invars]
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _gathered_shapes(sub, in_scan or eqn.primitive.name == "scan",
                             found)
    return found


@pytest.mark.parametrize("backend", ["ref", "pallas_interpret"])
def test_fixed_locals_are_gathered_once_a_segment(backend):
    """The unchanged ratings are gathered at most once a segment; since
    the record evaluates the objective on each worker shard's own data,
    not at all: no ``all_gather`` takes a shard of a local leaf, inside
    the clock loop or outside it."""
    app = _app(backend)
    ops.set_backend(backend)
    try:
        fn = PSRuntime(default_mesh(CFG.n_workers)).run_fn(app, essp(2), 3)
        state = fn.init_state(0)
        found = _gathered_shapes(jax.make_jaxpr(fn.run_from)(state).jaxpr)
    finally:
        ops.set_backend("auto")
    shard = {x.addressable_shards[0].data.shape
             for x in jax.tree.leaves(state.local)}
    assert not shard & (set(found[False]) | set(found[True]))


# ---------------------------------------------------------------------------
# the objective per worker shard, on four devices
# ---------------------------------------------------------------------------
MESHES = {"2x2": (2, 2), "4x1": (4, 1)}
SHARDED = """
import json, re, sys
sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp, numpy as np
from repro.apps.matfact import MFConfig, make_mf_app
from repro.core import essp, simulate
from repro.kernels import ops
from repro.launch.mesh import make_ps_mesh
from repro.psrun import PSRuntime
from repro.psrun.validate import INT_FIELDS

COLLECTIVE = re.compile(r"= (\\w+\\[[\\d,]*\\])\\S* (all-gather|all-reduce)"
                        r"(?:-start)?\\(.*op_name=\\"([^\\"]*)\\"")
def lda():
    from repro.apps.lda import LDAConfig, make_lda_app
    return make_lda_app(LDAConfig(n_docs=16, doc_len=24, vocab=48,
                                  n_topics=4, true_topics=4, n_workers=8))

out = {{}}
for app_name, backend in (("mf", "ref"), ("mf", "pallas_interpret"),
                          ("lda", "ref")):
    ops.set_backend(backend)
    app = lda() if app_name == "lda" else make_mf_app(MFConfig(
        n_rows=64, n_cols=200, rank=24, n_workers=8, batch=16))
    for mesh_name, (data, model) in {meshes!r}.items():
        rt = PSRuntime(make_ps_mesh(data=data, model=model))
        cfg = essp(3)
        tr = rt.run(app, cfg, 6, seed=1, record_views=True)
        # the last clock's losses, over the locals that clock ended with
        whole = jax.jit(app.loss)
        want = np.array([whole(tr.views0[-1], tr.locals_final),
                         whole(tr.x_final, tr.locals_final)])
        got = np.array([tr.loss_view[-1], tr.loss_ref[-1]])
        orc = jax.jit(lambda sd, x0, local0: simulate(
            app.__class__(**{{**app.__dict__, "x0": x0, "local0": local0}}),
            cfg, 6, seed=sd, record_views=True))(
            np.uint32(1), app.x0, app.local0)
        bits = all(np.asarray(getattr(tr, f)).tobytes()
                   == np.asarray(getattr(orc, f)).tobytes()
                   for f in ("loss_ref", "loss_view") + INT_FIELDS)
        fn = rt.run_fn(app, cfg, 6)
        st0 = fn.init_state(1)
        hlo = fn.lower(st0).compile().as_text()
        found = COLLECTIVE.findall(hlo)
        # the state a segment hands on is laid out as the program takes it
        _, st1 = fn.run_from(st0)
        placed = all(a.sharding.is_equivalent_to(b.sharding, a.ndim)
                     for a, b in zip(jax.tree.leaves(st0),
                                     jax.tree.leaves(st1)))
        ring = [list(s.data.shape) for s in st0.uring.addressable_shards]
        out[f"{{mesh_name}}-{{app_name}}-{{backend}}"] = dict(
            loss_rel=float(np.max(np.abs(got - want) / np.abs(want))),
            bits=bits,
            gathers=[s for s, op, name in found if op == "all-gather"],
            record=[[op, s] for s, op, name in found if "psrun.record" in name],
            locals=[f"{{a.dtype}}[{{','.join(map(str, a.shape))}}]"
                    for a in app.local0.values()],
            dense="dc" in app.local0, dim=app.dim, workers=app.n_workers,
            placed=placed, ring=ring, ring_shape=list(st0.uring.shape),
            model=model)
    ops.set_backend("auto")
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def sharded():
    """Four CPU devices, in a child process: this one has one."""
    import json
    import os
    import subprocess
    import sys
    from pathlib import Path
    src = str(Path(__file__).resolve().parents[1] / "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", SHARDED.format(src=src, meshes=MESHES)],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


CASES = [f"{m}-{a}" for m in MESHES
         for a in ("mf-ref", "mf-pallas_interpret", "lda-ref")]


@pytest.mark.parametrize("case", CASES)
def test_per_shard_objective_is_the_whole_objective(sharded, case):
    """Each worker shard sums the objective (MF's two forms, LDA's
    likelihood) over each of its own workers' data and one ``psum`` hands
    every shard all workers' sums: within f32 rounding of the objective
    over all the data, and bit for bit what the simulator gives, whatever
    the mesh."""
    r = sharded[case]
    assert r["dense"] == ("pallas" in case)
    assert r["loss_rel"] < 1e-6, r["loss_rel"]
    assert r["bits"]


@pytest.mark.parametrize("case", CASES)
def test_record_gathers_no_locals_and_no_views(sharded, case):
    """No all-gather anywhere in the segment builds a worker's locals
    (the ratings, the dense grids, LDA's tokens and counts) from other
    shards; the record gathers
    only the table and moves reader 0's view alone; its objective is one
    all-reduce of a ``[2, P]`` block of per-worker sums."""
    r = sharded[case]
    P, d = r["workers"], r["dim"]
    assert not set(r["gathers"]) & set(r["locals"])
    assert ["all-gather", f"f32[{P},{d}]"] not in r["record"]
    assert r["record"].count(["all-reduce", f"f32[2,{P}]"]) == 1
    assert ["all-reduce", f"f32[{d}]"] in r["record"]
    assert [op for op, _ in r["record"]].count("all-gather") == 1


@pytest.mark.parametrize("case", CASES)
def test_initial_state_is_placed_as_the_program_takes_it(sharded, case):
    """``init_state`` lays every leaf out as the segment program takes and
    returns it: each device holds its own shard of the ring, over
    ``"model"``, and no device holds the whole."""
    r = sharded[case]
    W, P, dpad = r["ring_shape"]
    assert r["placed"]
    assert r["ring"] == [[W, P, dpad // r["model"]]] * 4
