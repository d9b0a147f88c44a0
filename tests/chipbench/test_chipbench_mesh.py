"""The four-chip cell ``mf-netflix-flat2x2.essp3`` on the CPU, at a size a
test run holds.

A child process with four CPU devices (this one has one) runs the cell on
its 2x2 mesh:

- the data made on the mesh (`matfact_mesh.build_app`) equal the plain
  generator's (`matfact_mesh.make_data`) bit for bit, ratings sharded over
  ``"data"``;
- a sound run compares its first clocks with the plain reference and comes
  out correct under the cell's limits, on both forms of the objective
  (the per-rating gather form and the dense grid);
- with the exchange over ``"data"`` left out, it does not.

In this process: the control (the reference in bfloat16) and the faults
planted in the reference are not correct under the cell's limits; the
ratings' noise is finite at every 32-bit draw; the per-layer readers the
cell reports read what they should from a reduced trace; and a program
without the data-taking MF constructor fails in set-up.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import types
from pathlib import Path

import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import compare, run  # noqa: E402
from chipbench.apps import matfact_mesh as mm  # noqa: E402

CELL = "mf-netflix-flat2x2.essp3"
SEED = 2**31 + 7919
SMALL = dict(n_rows=128, n_cols=96, rank=16, batch=32, density=0.2)

CHILD = """
import json, sys, types
sys.path[:0] = [{root!r}, {src!r}]
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P_
from chipbench import run
from chipbench.apps import matfact_mesh as mm
from repro.kernels import ops
from repro.psrun import runtime

def small():
    cell = run.load_cell({cell!r})
    cell.config = dict(cell.config, **{small})
    cell.traffic = dict(cell.traffic, segment_clocks=4)
    return cell

out = {{}}
cell = small()
app = mm.build_app(cell.config)
made = (app.x0, app.local0["ii"], app.local0["jj"], app.local0["vv"])
plain = mm.make_data(cell.config)
out["data_equal"] = all(np.asarray(a).tobytes() == np.asarray(b).tobytes()
                        for a, b in zip(made, plain))
mesh = runtime.make_ps_mesh(devices=jax.devices()[:4], data=2, model=2)
out["data_sharded"] = all(
    a.sharding.is_equivalent_to(NamedSharding(mesh, P_("data")), a.ndim)
    for a in app.local0.values())

real = jax.lax.all_gather

def gather(x, axis_name, *, axis=0, tiled=False, **kw):
    # the exchange over the workers' axis left out: each chip fills the
    # other chips' rows with its own
    if tuple(axis_name) == ("data",) and tiled:
        return jnp.concatenate([x] * 2, axis=axis)
    return real(x, axis_name, axis=axis, tiled=tiled, **kw)

lax = types.SimpleNamespace(**{{**vars(jax.lax), "all_gather": gather}})
broken = types.SimpleNamespace(**{{**vars(jax), "lax": lax}})
for name, backend, jax_seen in (("sound_gather", "ref", jax),
                                ("sound_dense", "pallas_interpret", jax),
                                ("exchange_left_out", "ref", broken)):
    ops.set_backend(backend)
    runtime.jax = jax_seen
    res = run.run_cell(small(), {seed}, 0.5, False, jax.devices()[:4])
    out[name] = [res["correct"], {{k: c["value"] for k, c in res["checks"].items()}}]
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def child(tmp_path_factory):
    """The child's readings: one run of the script, shared by the cases."""
    script = tmp_path_factory.mktemp("mesh") / "mesh.py"
    script.write_text(CHILD.format(root=str(ROOT), src=str(ROOT / "src"),
                                   cell=CELL, small=SMALL, seed=SEED))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, str(script)], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("reading", ["data_equal", "data_sharded"])
def test_mesh_made_data(child, reading):
    assert child[reading] is True


@pytest.mark.parametrize("run_name,correct", [("sound_gather", True),
                                              ("sound_dense", True),
                                              ("exchange_left_out", False)])
def test_four_device_run(child, run_name, correct):
    ok, checks = child[run_name]
    assert ok is correct, checks


@pytest.mark.parametrize("kind", ["control", "half_batch", "doubled_answer"])
def test_control_and_faults_are_not_correct(kind):
    cell = run.load_cell(CELL)
    cell.config = dict(cell.config, **SMALL)
    cell.traffic = dict(cell.traffic, segment_clocks=4)
    n = run.SETUP_SEGMENTS * cell.traffic["segment_clocks"]
    ref = mm.reference(cell.config, cell.traffic, SEED, n)
    kw = dict(dtype=jnp.bfloat16) if kind == "control" else dict(fault=kind)
    got = mm.reference(cell.config, cell.traffic, SEED, n, **kw)
    ok, checks = compare.judge(compare.gaps(got, ref), cell.limits)
    assert not ok, checks


def test_rating_noise_is_finite_at_the_extreme_bits():
    """Every 32-bit draw gives a finite normal: at 27 million ratings the
    all-ones draw turns up."""
    import numpy as np
    bits = jnp.array([0, 1, 2**31, 2**32 - 2, 2**32 - 1], jnp.uint32)
    z = np.asarray(mm._normal_from_bits(bits))
    assert np.all(np.isfinite(z))
    assert z[0] == -z[-1] and z[0] < -5


def _ctx(reduced):
    from chipbench.apps import matfact as mfa
    cell = run.load_cell(CELL)
    work = mfa.work_counts(cell.config, cell.traffic, cell.config["mesh"])
    return run.MetricContext(reduced=reduced, clocks=30, compile_s=1.0,
                             kind="TPU v5 lite", chips=4, work=work), work


# op names as the trace gives them (HLO text); a fusion that takes a
# gather's result as an operand, and the loop's ``while``, which holds
# the whole clock, are not collectives
GATHER = ("%all-gather.17 = f32[2,4,14884200]{2,1,0} all-gather("
          "f32[1,4,14884200]{2,1,0} %p.1), dimensions={0}")
PSUM = "%all-reduce.3 = f32[2,8]{1,0} all-reduce(f32[2,8]{1,0} %s.2)"
FUSION = ("%fusion.9 = f32[4,14884200]{1,0} fusion(f32[2,4,14884200]{2,1,0} "
          "%all-gather.17), kind=kLoop, calls=%fused_computation.9")
WHILE = "%while.2 = (s32[]) while((s32[]) %t.1), condition=%c, body=%b"
RING_VIEW = ("%custom-call.5 = f32[4,7442176]{1,0} custom-call("
             "s32[5,1]{1,0} %a, s32[4,8]{1,0} %b, f32[1,7442176]{1,0} %c, "
             "f32[5,8,7442176]{2,1,0} %d), "
             "custom_call_target=\"tpu_custom_call\"")
NORMS = ("%custom-call.6 = f32[6,8]{1,0} custom-call(s32[5,1]{1,0} %a, "
         "s32[1,1]{1,0} %e, f32[5,8,7442176]{2,1,0} %d), "
         "custom_call_target=\"tpu_custom_call\"")
REDUCED = {"window_s": 4.0, "busy_s": 3.0, "n_devices": 4,
           "op_time": {GATHER: [120, 0.8], PSUM: [120, 0.4],
                       FUSION: [120, 2.0], WHILE: [4, 11.9],
                       RING_VIEW: [120, 2.2], NORMS: [120, 2.1]}}


def test_collective_share_reads_the_collectives_self_time():
    """The gather's and the psum's self time, over 4 chips and the window;
    the fusion that reads the gather and the ``while`` do not count."""
    ctx, _ = _ctx(REDUCED)
    value, unit = run.read_metric("collective_share", ctx)
    assert unit == "%" and value == pytest.approx(100 * 1.2 / 4 / 4.0)
    ctx.reduced = dict(REDUCED, op_time={FUSION: [120, 2.0],
                                         WHILE: [4, 11.9]})
    assert run.read_metric("collective_share", ctx)[0] is None


def _by_hand(name, work):
    if name == "compile_s":
        return 1.0
    if name == "idle_share":
        return 100 * (1 - 3.0 / 4.0)
    if name == "mfu.clock":
        w = work["clock"]
        # the clock's work for all four chips, at four chips' peaks
        return 100 * max(w["flops"] / (4 * 197e12),
                         w["bytes"] / (4 * 819e9)) * 30 / 4.0
    # a kernel's work per call on one chip (its shard of d), over the
    # mean time of a call on a chip
    w, secs = ((work["ring_view"], 2.2) if name == "ring_view_roofline"
               else (work["vap_suffix_norms"], 2.1))
    return 100 * max(w["flops"] / 197e12, w["bytes"] / 819e9) * 120 / secs


ACCEPTED = ["compile_s", "idle_share", "mfu.clock", "ring_view_roofline",
            "vap_suffix_norms_roofline"]


@pytest.mark.parametrize("name", ACCEPTED)
def test_accepted_readers_read_the_four_chip_cell(name):
    """Each accepted per-layer metric that lists the four-chip cell reads
    its trace as by hand, at the cell's work per chip."""
    ctx, work = _ctx(REDUCED)
    value, _ = run.read_metric(name, ctx)
    assert value == pytest.approx(_by_hand(name, work))
    assert CELL in next(m for m in run._load_json(
        ROOT / "BENCHMARK.json")["per_layer"] if m["name"] == name)[
            "workloads"]


def test_parent_without_the_data_taking_constructor_fails_in_set_up(
        monkeypatch):
    """A program whose MF app takes no data (``mf_app``) fails at once."""
    import repro.apps.matfact as program
    stub = types.ModuleType("repro.apps.matfact")
    stub.MFConfig = program.MFConfig
    monkeypatch.setitem(sys.modules, "repro.apps.matfact", stub)
    with pytest.raises(ImportError):
        mm.build_app(run.load_cell(CELL).config)
