"""The chip benchmark's harness on the CPU, at a size a test run holds.

- Off TPU the command exits non-zero and prints no result; so it does in a
  directory that holds only ``BENCHMARK.json`` and the benchmark's files.
- A run driven past the chip check, on a small MF shape, compares its first
  clocks with the plain reference and comes out correct.
- The control, the reference in bfloat16 put in the program's place, comes
  out not correct under the cells' limits.
- With the timed path broken underneath (the state handed on unchanged,
  half of each worker's batch left out, one worker's update doubled where it
  is made, the exchange between the chips left out), ``correct`` is false.
"""
from __future__ import annotations

import dataclasses
import json
import os
import shutil
import subprocess
import sys
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import compare, run  # noqa: E402
from chipbench.apps import matfact as mfa  # noqa: E402

SEED = 2**31 + 4099          # seeds past 32 signed bits must work
SMALL = dict(n_rows=128, n_cols=96, rank=16, batch=32, density=0.2)


def small_cell(workload="mf-netflix.essp3", mesh=None) -> run.Cell:
    cell = run.load_cell(workload)
    cell.config = dict(cell.config, **SMALL,
                       mesh=mesh or {"data": 1, "model": 1})
    cell.traffic = dict(cell.traffic, segment_clocks=4)
    return cell


def _command(cwd, env=None):
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "mf-netflix.essp3", "--seed", str(SEED), "--seconds", "1",
         "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def _no_result(proc):
    assert proc.returncode != 0
    assert '"metrics"' not in proc.stdout


def test_no_chip_exits_nonzero_without_metrics():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = _command(ROOT, env)
    _no_result(proc)
    assert "no TPU" in proc.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for path in bench["paths"]:
        shutil.copytree(ROOT / path, tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    _no_result(_command(tmp_path, dict(os.environ, JAX_PLATFORMS="cpu")))


def _run(cell, **kw):
    return run.run_cell(cell, SEED, 0.5, False, jax.devices()[:1], **kw)


def test_sound_run_is_correct():
    res = _run(small_cell())
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1
    assert set(res["metrics"]) == {"samples_per_s", "time_to_loss_s",
                                   "setup_s"}
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("workload", ["mf-netflix.essp3", "mf-netflix.bsp"])
def test_control_is_not_correct(workload):
    cell = small_cell(workload)
    n = run.SETUP_SEGMENTS * cell.traffic["segment_clocks"]
    ref = mfa.reference(cell.config, cell.traffic, SEED, n)
    control = mfa.reference(cell.config, cell.traffic, SEED, n,
                            dtype=jnp.bfloat16)
    ok, checks = compare.judge(compare.gaps(control, ref), cell.limits)
    assert not ok, checks


def test_state_unchanged_is_not_correct():
    from chipbench.calibrate import unchanged
    assert not _run(small_cell(), wrap_segment=unchanged)["correct"]


@pytest.mark.parametrize("extra", [{"straggler_workers": 2},
                                   {"model": "ssp"}])
def test_traffic_outside_the_reference_is_refused(extra):
    traffic = dict(run.load_cell("mf-netflix.essp3").traffic, **extra)
    with pytest.raises(ValueError):
        run.consistency(traffic)


def test_compile_cache_takes_the_directory_it_is_given(tmp_path,
                                                       monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert run.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert run.enable_compile_cache() == str(run.ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_crossing_interpolates_between_clocks():
    losses = np.array([5.0, 4.0, 3.0, 2.0])
    assert run.crossing(losses, 6.0, 3.5, 10.0, 4.0) == pytest.approx(12.5)
    assert run.crossing(losses, 6.0, 5.5, 0.0, 4.0) == pytest.approx(0.5)
    assert run.crossing(losses, 6.0, 1.0, 0.0, 4.0) is None


def _app_mod(build_app):
    return types.SimpleNamespace(**{**vars(mfa), "build_app": build_app})


def test_half_batch_is_not_correct():
    def half(config):
        return mfa.build_app(dict(config, batch=config["batch"] // 2))
    assert not _run(small_cell(), app_mod=_app_mod(half))["correct"]


def test_altered_answer_is_not_correct():
    def altered(config):
        app = mfa.build_app(config)

        def update(view, local, wid, clock, rng):
            u, local = app.worker_update(view, local, wid, clock, rng)
            return u * jnp.where(wid == 0, 2.0, 1.0), local
        return dataclasses.replace(app, worker_update=update)
    assert not _run(small_cell(), app_mod=_app_mod(altered))["correct"]


EXCHANGE_LEFT_OUT = """
import sys, types
sys.path[:0] = [{root!r}, {src!r}]
import jax, jax.numpy as jnp
from repro.psrun import runtime
from chipbench import run
real = jax.lax.all_gather

def gather(x, axis_name, *, axis=0, tiled=False, **kw):
    # the exchange over the workers' axis left out: each chip fills the
    # other chips' rows with its own
    if tuple(axis_name) == ("data",) and tiled:
        return jnp.concatenate([x] * 2, axis=axis)
    return real(x, axis_name, axis=axis, tiled=tiled, **kw)

lax = types.SimpleNamespace(**{{**vars(jax.lax), "all_gather": gather}})
broken = types.SimpleNamespace(**{{**vars(jax), "lax": lax}})
for name, jax_seen in (("sound", jax), ("broken", broken)):
    runtime.jax = jax_seen
    cell = run.load_cell("mf-netflix.essp3")
    cell.config = dict(cell.config, **{small}, mesh={{"data": 2, "model": 2}})
    cell.traffic = dict(cell.traffic, segment_clocks=4)
    res = run.run_cell(cell, {seed}, 0.5, False, jax.devices()[:4])
    print(name, res["correct"])
"""


def test_exchange_left_out_is_not_correct(tmp_path):
    """Four CPU devices, in a child process: this one has one."""
    script = tmp_path / "exchange.py"
    script.write_text(EXCHANGE_LEFT_OUT.format(
        root=str(ROOT), src=str(ROOT / "src"), small=SMALL, seed=SEED))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, str(script)], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout.split() == ["sound", "True", "broken", "False"]
