"""Batched sweep engine + Pallas ps_view kernels.

The engine contract: a batched `sweep` is *bit-identical* (same seed, same
config, same ring window) to a standalone `simulate` call, for every
consistency model, while compiling once per config family.  The Pallas
ring-view / suffix-norm bodies must match the jnp references under
``interpret=True``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bsp, essp, simulate, ssp, vap
from repro.core.consistency import ConsistencyConfig
from repro.core.sweep import family_window, stack_configs, sweep, trace_count
from repro.kernels import ops, ps_view, ref

FLOAT_FIELDS = ("loss_ref", "loss_view", "u_l2", "intransit_inf", "x_final")
INT_FIELDS = ("staleness", "forced", "delivered")


def assert_traces_identical(got, want, context=""):
    for name in INT_FIELDS + FLOAT_FIELDS:
        a, b = np.asarray(getattr(got, name)), np.asarray(getattr(want, name))
        np.testing.assert_array_equal(a, b, err_msg=f"{context}:{name}")


def assert_traces_close(got, want, context=""):
    """Decisions exact, floats within a strict ulp budget — the contract
    for VAP under a *multi-device* sweep: XLA's backend instruction-selects
    the scan body differently when the enforcement graph is present
    (replaying the worker update on bit-identical recorded inputs
    reproduces the plain-jit value, and optimization barriers leave the
    drift byte-identical — backend codegen, not semantic drift; see
    `psrun.validate`).  App-dependent: MF/LDA are exactly stable
    (`test_sweep_vap_mf_bit_identical_sharded`), the quad app drifts
    ~ulp/clock.  Single-device sweeps stay bit-identical."""
    from repro.psrun.validate import VAP_ULP_BUDGET, trace_max_ulp
    for name in INT_FIELDS:
        a, b = np.asarray(getattr(got, name)), np.asarray(getattr(want, name))
        np.testing.assert_array_equal(a, b, err_msg=f"{context}:{name}")
    ulps = trace_max_ulp(got, want)   # field-scale ulp (see its docstring)
    worst = max(ulps.values())
    assert worst <= VAP_ULP_BUDGET, (context, ulps)


FAMILY_CASES = [
    ("bsp", [bsp(), bsp(push_prob=0.5)]),
    ("ssp", [ssp(2), ssp(5)]),
    ("essp", [essp(2, push_prob=0.6), essp(5)]),
    ("async", [ConsistencyConfig(model="async", push_prob=0.4),
               ConsistencyConfig(model="async", push_prob=0.9)]),
    ("vap", [vap(0.3, staleness=5), vap(1.0, staleness=5)]),
]


@pytest.mark.parametrize(("model", "configs"),
                         FAMILY_CASES, ids=[m for m, _ in FAMILY_CASES])
def test_sweep_bit_identical_to_simulate(quad_app, model, configs):
    """Each (config, seed) trace of a batched sweep equals a standalone
    `simulate` run bit for bit (with the family's harmonized window)."""
    seeds = [0, 3]
    res = sweep(quad_app, configs, 25, seeds=seeds)
    assert res.n_compiles == 1
    check = (assert_traces_close
             if model == "vap" and len(jax.devices()) > 1
             else assert_traces_identical)
    for i, _cfg in enumerate(configs):
        assert res.harmonized[i].effective_window == family_window(configs)
        for j, sd in enumerate(seeds):
            want = jax.jit(
                lambda c=res.harmonized[i], s=sd:
                simulate(quad_app, c, 25, seed=s))()
            check(res.trace(i, j), want,
                  context=f"{model}[{i}] seed={sd}")


def test_sweep_vap_mf_bit_identical_sharded():
    """The acceptance app (MF) is *bit-identical* under a sharded VAP sweep
    — the multi-device codegen drift pinned above is quad-app-specific, and
    this holds the line on the apps the paper's claims are measured on."""
    from repro.apps.matfact import MFConfig, make_mf_app
    app = make_mf_app(MFConfig(n_rows=64, n_cols=64, rank=8, true_rank=8,
                               n_workers=4, batch=64, lr=0.5))
    configs = [vap(0.5, staleness=4), vap(1.0, staleness=4)]
    res = sweep(app, configs, 12, seeds=[0, 3])
    for i in range(len(configs)):
        for j, sd in enumerate([0, 3]):
            want = jax.jit(
                lambda c=res.harmonized[i], s=sd:
                simulate(app, c, 12, seed=s))()
            assert_traces_identical(res.trace(i, j), want,
                                    context=f"mf vap[{i}] seed={sd}")


def test_sweep_groups_mixed_families(quad_app):
    """Configs interleaved across families come back aligned, one compile
    per family."""
    configs = [bsp(), ssp(3), essp(3), ssp(6), bsp(push_prob=0.5)]
    n0 = trace_count()
    res = sweep(quad_app, configs, 15, seeds=2)
    assert res.n_compiles == 3                    # bsp, ssp, essp
    assert trace_count() - n0 == 3
    # ssp members share one harmonized window; bsp keeps its own
    assert res.harmonized[1].window == res.harmonized[3].window == 8
    assert res.harmonized[0].window == 2
    # alignment: each row reproduces its own config
    want = jax.jit(lambda: simulate(quad_app, res.harmonized[3], 15, seed=1))()
    assert_traces_identical(res.trace(3, 1), want, context="mixed ssp(6)")


def test_sweep_knobs_are_traced_not_recompiled(quad_app):
    """The whole point: varying every numeric knob stays inside one
    compiled program."""
    configs = [essp(s, push_prob=p, straggler_prob=q,
                    straggler_workers=w, straggler_rate=0.3)
               for s, p, q, w in [(1, 0.9, 0.0, 0), (4, 0.5, 0.2, 1),
                                  (7, 0.7, 0.1, 2), (2, 0.3, 0.3, 3)]]
    n0 = trace_count()
    res = sweep(quad_app, configs, 10, seeds=3)
    assert res.n_compiles == 1
    assert trace_count() - n0 == 1
    assert np.isfinite(np.asarray(res.traces[0].loss_ref)).all()


def test_stack_configs_rejects_cross_family():
    with pytest.raises(ValueError, match="across families"):
        stack_configs([bsp(), ssp(3)])


def test_config_window_required_when_staleness_traced():
    cfg = ssp(3).replace(staleness=jnp.asarray([1, 2]))
    with pytest.raises(ValueError, match="effective_window"):
        _ = cfg.effective_window
    assert cfg.replace(window=9).effective_window == 9


def _ring_inputs(W=7, P=8, d=256, c=13, seed=0):
    rng = np.random.default_rng(seed)
    uring = jnp.asarray(rng.normal(size=(W, P, d)).astype(np.float32))
    clocks = c - 1 - rng.permutation(W)            # distinct ring clocks
    clocks[rng.random(W) < 0.3] = -(10**9)         # some empty slots
    uclock = jnp.asarray(clocks.astype(np.int32))
    cview = jnp.asarray(rng.integers(-1, c, size=(P, P)).astype(np.int32))
    base = jnp.asarray(rng.normal(size=(d,)).astype(np.float32))
    return base, uring, uclock, cview, jnp.int32(c)


# d not a multiple of 128 (nor of the 256-lane block): a ragged last block
RAGGED = [(5, 8, 1000), (2, 8, 300), (7, 4, 1153)]
KERNEL_SHAPES = [(7, 8, 256), (3, 4, 128), (12, 16, 512)] + RAGGED


@pytest.mark.parametrize("shape", KERNEL_SHAPES)
def test_ring_view_kernel_matches_ref(shape):
    """At `block_lanes`' block (one step at these widths) and at 256 lanes
    (several steps, the last ragged where 256 does not divide d)."""
    W, P, d = shape
    base, uring, uclock, cview, _ = _ring_inputs(W, P, d)
    want = ref.ring_view(base, uring, uclock, cview)
    for block_d in (None, 256):
        got = ps_view.ring_view(base, uring, uclock, cview, block_d=block_d,
                                interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5, err_msg=str(block_d))


@pytest.mark.parametrize("shape", KERNEL_SHAPES)
def test_vap_suffix_norms_kernel_matches_ref(shape):
    W, P, d = shape
    _, uring, uclock, _, c = _ring_inputs(W, P, d)
    want = ref.vap_suffix_norms(uring, uclock, c)
    for block_d in (None, 256):
        got = ps_view.vap_suffix_norms(uring, uclock, c, block_d=block_d,
                                       interpret=True)
        assert got.shape == (W + 1, P)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6, err_msg=str(block_d))


def _pad_128(x):
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, (-x.shape[-1]) % 128)])


def _ragged_inputs(W, P, d, peak_last):
    """`_ring_inputs`, every slot holding one of the newest W clocks; with
    ``peak_last`` each slot's largest |value| sits in the last lane < d."""
    base, uring, _, cview, c = _ring_inputs(W, P, d)
    uclock = jnp.arange(int(c) - 1, int(c) - 1 - W, -1, dtype=jnp.int32)
    if peak_last:
        uring = uring.at[:, :, d - 1].set(
            1e3 * (1.0 + jnp.arange(W * P, dtype=jnp.float32).reshape(W, P)))
    return base, uring, uclock, cview, c


# Blocks of 128, 256 and 1024 lanes and `block_lanes`' one block.  The
# interpreter reads lanes past d as NaN, so a lane the kernel failed to
# mask or to drop would show.
BLOCKS = (128, 256, 1024, None)


@pytest.mark.parametrize("peak_last", [False, True])
@pytest.mark.parametrize("shape", RAGGED)
def test_vap_suffix_norms_bit_equal_across_blocks(shape, peak_last):
    """Any block gives the norms bit for bit, and they are the norms of the
    ring padded with zeros to 128 lanes at a 128-lane block (how the
    kernel was called before its last block could be ragged)."""
    W, P, d = shape
    _, uring, uclock, _, c = _ragged_inputs(W, P, d, peak_last)
    want = np.asarray(ps_view.vap_suffix_norms(_pad_128(uring), uclock, c,
                                               block_d=128, interpret=True))
    for block_d in BLOCKS:
        got = ps_view.vap_suffix_norms(uring, uclock, c, block_d=block_d,
                                       interpret=True)
        assert np.array_equal(np.asarray(got), want), block_d
    # the kernel's arithmetic in float32 numpy: per k, every slot's masked
    # add in slot order, then the lane max of |suffix|
    ring, clocks = np.asarray(uring), np.asarray(uclock)
    suffix = np.zeros((P, d), np.float32)
    peaks = [np.zeros(P, np.float32)]
    for k in range(1, W + 1):
        for w in range(W):
            suffix = suffix + np.where(clocks[w] == int(c) - k, ring[w],
                                       np.float32(0))
        peaks.append(np.abs(suffix).max(axis=-1))
    assert np.array_equal(want, np.stack(peaks))
    if peak_last:                       # the last lane holds every norm
        np.testing.assert_array_equal(
            want[1:], np.abs(np.cumsum(np.asarray(uring[:, :, d - 1]), 0)))


@pytest.mark.parametrize("peak_last", [False, True])
@pytest.mark.parametrize("shape", RAGGED)
def test_ring_view_bit_equal_across_blocks(shape, peak_last):
    """As for the norms: every block gives the padded ring's views at 128
    lanes, bit for bit (each lane's dot does not depend on the block)."""
    W, P, d = shape
    base, uring, uclock, cview, _ = _ragged_inputs(W, P, d, peak_last)
    want = np.asarray(ps_view.ring_view(_pad_128(base), _pad_128(uring),
                                        uclock, cview, block_d=128,
                                        interpret=True))[:, :d]
    for block_d in BLOCKS:
        got = ps_view.ring_view(base, uring, uclock, cview, block_d=block_d,
                                interpret=True)
        assert got.shape == (P, d)
        assert np.array_equal(np.asarray(got), want), block_d


# (W, P, R, d) of the cells' rings: ESSP(3) and BSP over MF at rank 100
# with 32,768 users and 17,770 items on one chip, and ESSP(3) over 131,072
# users split over a mesh axis of 2 with 4 readers a data shard
CELL_RINGS = [(5, 8, 8, 5_053_800), (2, 8, 8, 5_053_800),
              (5, 8, 4, 7_442_100)]


@pytest.mark.parametrize(("W", "P", "R", "d"),
                         CELL_RINGS + [(7, 8, 8, 256), (3, 4, 4, 128),
                                       (12, 16, 16, 512), (5, 8, 8, 1000),
                                       (7, 4, 4, 1153)])
def test_block_lanes(W, P, R, d):
    """A multiple of 128 lanes up to d rounded up to 128, whose
    double-buffered blocks fit the budget: hundreds of steps or fewer a
    call at the cells' widths, one at the tests' small ones."""
    padded = -(-d // 128) * 128
    for readers in (R, 0):              # ring_view; vap_suffix_norms
        block = ps_view.block_lanes(W, P, readers, d)
        assert block % 128 == 0 and 128 <= block <= padded
        rows = W * (-(-P // 8) * 8)
        if readers:
            rows += 8 + -(-readers // 8) * 8
        assert 2 * 4 * rows * block <= ps_view.VMEM_BUDGET
        steps = -(-d // block)
        assert steps == 1 if d <= 2048 else 10 <= steps <= 1000


def test_suffix_norms_semantics():
    """norms[k,q] really is the inf-norm of the k-newest-clock aggregate."""
    W, P, d = 4, 2, 128
    c = 10
    uring = jnp.zeros((W, P, d)).at[:, :, 0].set(
        jnp.asarray([[1.0, -1.0], [2.0, 0.5], [-4.0, 0.25], [8.0, 0.125]]))
    uclock = jnp.asarray([c - 1, c - 2, c - 3, c - 4], jnp.int32)
    norms = np.asarray(ref.vap_suffix_norms(uring, uclock, jnp.int32(c)))
    np.testing.assert_allclose(norms[:, 0], [0, 1, 3, 1, 7])
    np.testing.assert_allclose(norms[:, 1], [0, 1, 0.5, 0.25, 0.125])


def test_ops_dispatch_ps_view():
    """`ops.set_backend("pallas_interpret")` routes the simulator's hot path
    through the Pallas bodies; traces must match the ref backend."""
    base, uring, uclock, cview, c = _ring_inputs()
    try:
        ops.set_backend("pallas_interpret")
        got_v = ops.ring_view(base, uring, uclock, cview)
        got_n = ops.vap_suffix_norms(uring, uclock, c)
        ops.set_backend("ref")
        want_v = ops.ring_view(base, uring, uclock, cview)
        want_n = ops.vap_suffix_norms(uring, uclock, c)
    finally:
        ops.set_backend("auto")
    np.testing.assert_allclose(np.asarray(got_v), np.asarray(want_v),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_n), np.asarray(want_n),
                               rtol=1e-5, atol=1e-6)


def test_simulate_through_pallas_interpret_backend():
    """Full simulate with the Pallas bodies (interpret) vs the jnp ref, on a
    kernel-aligned app (d % 128 == 0)."""
    P, d = 8, 128

    def worker_update(view, local, _wid, clock, rng):
        g = view + 0.05 * jax.random.normal(rng, view.shape)
        return -(0.3 / jnp.sqrt(1.0 + clock)) * g / P, local

    from repro.core.ps import PSApp
    app = PSApp(name="quad128", dim=d, n_workers=P, x0=jnp.ones((d,)) * 2.0,
                local0={"_": jnp.zeros((P, 1))},
                worker_update=worker_update,
                loss=lambda x, l: jnp.sum(jnp.square(x)))
    cfg = vap(0.5, staleness=4)
    try:
        ops.set_backend("ref")
        want = jax.jit(lambda: simulate(app, cfg, 6))()
        ops.set_backend("pallas_interpret")
        got = jax.jit(lambda: simulate(app, cfg, 6))()
    finally:
        ops.set_backend("auto")
    for name in FLOAT_FIELDS:
        np.testing.assert_allclose(
            np.asarray(getattr(got, name)), np.asarray(getattr(want, name)),
            rtol=1e-5, atol=1e-5, err_msg=name)
