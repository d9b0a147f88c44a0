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
        want = jax.jit(_gather_loss)(x, app.local0)
        assert np.asarray(got).tobytes() == np.asarray(want).tobytes()


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
    """The record's gather of the unchanged ratings sits before the clock
    loop, not in it: across worker shards it moves them once a segment."""
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
    assert shard <= set(found[False])
    assert not shard & set(found[True])
