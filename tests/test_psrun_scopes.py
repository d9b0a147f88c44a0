"""The clock step's stage scopes, read from the compiled program.

``psrun/runtime.py`` runs each stage of the clock under
``jax.named_scope("psrun.<stage>")``; a profiler trace charges each device
op to a stage through the op's ``op_name``.  These tests compile the
runtime for a tiny MF app and read every instruction's ``op_name`` from
``compile().as_text()`` (the lowered text carries no names without debug
info): every instruction the step body emits carries one of the five
scopes, apart from the scan's own plumbing, listed here by name.
"""
import re

import pytest

from repro.apps.matfact import MFConfig, make_mf_app
from repro.core import bsp, essp, vap
from repro.core.consistency import compressed, podded
from repro.kernels import ops
from repro.psrun import PSRuntime, default_mesh

STAGES = ("enforce", "view", "update", "push", "record")
BODY = "jit(run)/while/body/"
# what ``lax.scan`` itself emits inside the loop body
PLUMBING = frozenset(BODY + p for p in (
    "dynamic_slice",           # reads the clock ``c`` from the scanned xs
    "broadcast_in_dim",        # gives a clock's record its leading axis
    "dynamic_update_slice",    # and stacks it into the ys
    "add",                     # the loop counter
    "closed_call"))            # constants of the body's call
OP_NAME = re.compile(r'op_name="([^"]*)"')
SCOPE = re.compile(r"(?:^|/)psrun\.(\w+)(?=/|$)")

CONFIGS = {
    "essp": essp(2),
    "bsp": bsp(),
    "vap": vap(0.5),
    "int8-wire": compressed(podded(essp(2), n_pods=2, s_xpod=1),
                            agg_clocks=2, topk_frac=0.5, quant="int8"),
}


@pytest.fixture(scope="module")
def app():
    return make_mf_app(MFConfig(n_rows=16, n_cols=12, rank=4, n_workers=4,
                                batch=8))


def _op_names(app, cfg) -> list[str]:
    fn = PSRuntime(default_mesh(app.n_workers)).run_fn(app, cfg, 3)
    text = fn.lower(fn.init_state(0), cfg).compile().as_text()
    return OP_NAME.findall(text)


def _stage(op_name: str):
    m = SCOPE.search(op_name)
    return m.group(1) if m else None


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_every_step_op_carries_a_stage_scope(app, name):
    body = [n for n in _op_names(app, CONFIGS[name]) if n.startswith(BODY)]
    unscoped = sorted({n for n in body if _stage(n) is None} - PLUMBING)
    assert not unscoped, unscoped
    assert {_stage(n) for n in body} - {None} == set(STAGES)


def test_kernels_run_in_their_stages(app):
    """On a Pallas backend the named kernels sit in the stage that calls
    them: the suffix norms in enforce, the ring view in view, the delta
    pack in push."""
    ops.set_backend("pallas_interpret")
    try:
        names = _op_names(app, CONFIGS["int8-wire"])
    finally:
        ops.set_backend("auto")
    where = {}
    for n in names:
        parts = n.split("/")
        for kernel in ("vap_suffix_norms", "ring_view", "delta_pack"):
            if kernel in parts:
                where.setdefault(kernel, set()).add(_stage(n))
    assert where == {"vap_suffix_norms": {"enforce"}, "ring_view": {"view"},
                     "delta_pack": {"push"}}


def test_mf_objective_kernel_runs_in_record():
    """An MF app built on a Pallas backend evaluates its objective with the
    ``mf_sse`` kernel (vmapped over the workers), twice a clock (the table
    and worker 0's view), in ``psrun.record`` and in no other stage.  Each
    worker's rows fold into the kernel's sublane groups of 8."""
    ops.set_backend("pallas_interpret")
    try:
        dense = make_mf_app(MFConfig(n_rows=32, n_cols=12, rank=4,
                                     n_workers=4, batch=8))
        names = _op_names(dense, CONFIGS["essp"])
    finally:
        ops.set_backend("auto")
    kernel = {"mf_sse", "vmap(mf_sse)"}
    stages = {_stage(n) for n in names if kernel & set(n.split("/"))}
    assert stages == {"record"}
