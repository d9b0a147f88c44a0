"""Optimizers, schedules, and the SSP gradient FIFO."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim.optimizers import (adamw, apply_updates, cosine_schedule,
                                    inv_sqrt_schedule, momentum, sgd)
from repro.psdist.grad_sync import (GradSync, bucket_assignment, init_fifo,
                                    push_pop, sync_gradients)


def _quad_min(opt, steps=200):
    params = {"w": jnp.ones((8,)) * 3.0, "b": jnp.ones((1,))}
    state = opt.init(params)

    def grad_fn(p):
        return jax.grad(lambda q: jnp.sum(jnp.square(q["w"]))
                        + jnp.sum(jnp.square(q["b"])))(p)

    @jax.jit
    def step(params, state):
        upd, state = opt.update(grad_fn(params), state, params)
        return apply_updates(params, upd), state

    for _ in range(steps):
        params, state = step(params, state)
    return params


@pytest.mark.parametrize("opt", [sgd(0.1), momentum(0.05), adamw(0.05)])
def test_optimizers_minimize_quadratic(opt):
    params = _quad_min(opt)
    assert float(jnp.abs(params["w"]).max()) < 0.1


def test_adamw_bf16_states():
    opt = adamw(0.05, state_dtype=jnp.bfloat16)
    params = _quad_min(opt)
    assert float(jnp.abs(params["w"]).max()) < 0.2
    st = opt.init({"w": jnp.ones((4,))})
    assert st["m"]["w"].dtype == jnp.bfloat16


def test_schedules():
    cos = cosine_schedule(1.0, warmup=10, total=100)
    assert float(cos(jnp.int32(0))) < 0.2
    assert float(cos(jnp.int32(10))) == pytest.approx(1.0, abs=0.05)
    assert float(cos(jnp.int32(99))) < 0.2
    inv = inv_sqrt_schedule(1.0)
    assert float(inv(jnp.int32(0))) == 1.0
    assert float(inv(jnp.int32(3))) == 0.5


def test_fifo_warmup_and_order():
    """SSP FIFO: nothing applied for the first s steps; order preserved."""
    sync = GradSync("ssp", staleness=2)
    params = {"w": jnp.zeros((3,))}
    fifo = init_fifo(sync, params)

    g1 = {"w": jnp.ones((3,)) * 1}
    g2 = {"w": jnp.ones((3,)) * 2}
    g3 = {"w": jnp.ones((3,)) * 3}

    out1, fifo, v1 = push_pop(fifo, g1)
    out2, fifo, v2 = push_pop(fifo, g2)
    out3, fifo, v3 = push_pop(fifo, g3)
    assert float(v1) == 0.0   # warm-up
    assert float(v2) == 0.0   # warm-up
    assert float(v3) == 1.0
    np.testing.assert_allclose(np.asarray(out3["w"]), 1.0)  # stalest first


def test_sync_gradients_bsp_identity():
    sync = GradSync("bsp")
    g = {"w": jnp.arange(4.0)}
    out, fifo, scale = sync_gradients(sync, g, None, data_axes=())
    np.testing.assert_allclose(np.asarray(out["w"]), np.arange(4.0))
    assert float(scale) == 1.0


def test_bucket_assignment_balanced():
    grads = {f"p{i}": jnp.zeros((sz,)) for i, sz in
             enumerate([100, 90, 50, 40, 30, 10, 5, 5])}
    assign = bucket_assignment(grads, 4)
    loads = [0] * 4
    import numpy as np_
    for (_k, v), b in zip(grads.items(), assign, strict=True):
        loads[b] += v.size
    assert max(loads) <= 2 * min(l for l in loads if l > 0)
    assert len(set(assign)) == 4


def test_essp_bucketed_psum_equals_fused():
    """Under shard_map on a 1-device mesh, bucketed pmean == fused pmean."""
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.psdist.grad_sync import psum_mean_bucketed

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    g = {"a": jnp.arange(8.0), "b": jnp.ones((4,)) * 2}

    def run(n_buckets):
        f = jax.shard_map(
            lambda t: psum_mean_bucketed(t, ("data",), n_buckets),
            mesh=mesh, in_specs=(P(),), out_specs=P(), check_vma=False)
        return f(g)

    r1, r4 = run(1), run(4)
    for k in g:
        np.testing.assert_allclose(np.asarray(r1[k]), np.asarray(r4[k]))
        np.testing.assert_allclose(np.asarray(r1[k]), np.asarray(g[k]))


def test_vap_schedule_utils(quad_app):
    from repro.core import vap as vap_mk, simulate
    from repro.core.valuebound import check_condition, sync_cost, v_schedule
    tr = jax.jit(lambda: simulate(quad_app, vap_mk(0.3, staleness=6), 50))()
    chk = check_condition(tr, 0.3)
    assert chk["violations"] == 0
    sc = sync_cost(tr)
    assert sc["forced_per_clock"] >= 0
    assert v_schedule(1.0, "constant")(100) == 1.0
    assert v_schedule(1.0, "inv_t")(0) == 1.0


def test_essp_exposure_model():
    """Eager bucketing reduces exposed collective time monotonically while
    total payload is fixed (the Fig 1-right intuition on pods)."""
    from repro.psdist.schedules import ScheduleModel, exposure_table
    rows = exposure_table(compute_s=1.0, collective_s=0.8)
    exposed = [r["exposed_s"] for r in rows]
    assert all(a >= b - 1e-9
               for a, b in zip(exposed, exposed[1:], strict=False))
    assert exposed[0] == pytest.approx(0.8)          # lazy: fully exposed
    # many buckets: only the last bucket's tail spills past compute
    assert exposed[-1] < 0.25
    # collective-dominated regime: overlap can't hide everything
    m = ScheduleModel(compute_s=0.2, collective_s=1.0, n_buckets=16)
    assert m.exposed_s() > 0.75
