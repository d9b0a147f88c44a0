"""The runtime's clock-step program, lowered on a 1x1 and a 2x2 mesh.

The benchmark's two programs (MF under ESSP(3) with a ring of 5 clocks,
and under BSP with a ring of 2; dense wire, no churn, no telemetry) lower
on both meshes, and so do the churned, wired, faulted and observed forks
of the clock step.  The child process prints a SHA-256 of each
``Lowered.as_text()``, so two commits can be compared program by program
(``python tests/test_clock_step_program.py`` prints them); the test pins
no hash, since the text depends on the JAX version.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

LOWER = """
import hashlib, json, sys
sys.path.insert(0, {src!r})
import jax
from repro.apps.matfact import MFConfig, make_mf_app
from repro.comm import wire
from repro.core.consistency import ConsistencyConfig
from repro.core.delays import make_churn
from repro.launch.mesh import make_ps_mesh
from repro.obs.metrics import ObsSpec
from repro.psrun import PSRuntime

T, P = 3, 8
app = make_mf_app(MFConfig(n_rows=64, n_cols=200, rank=24, n_workers=P,
                           batch=16))
DENSE = {{"essp3": ConsistencyConfig(model="essp", staleness=3, window=5,
                                    push_prob=0.9, straggler_prob=0.05),
         "bsp": ConsistencyConfig(model="bsp", staleness=0, window=2)}}
wired = ConsistencyConfig(model="essp", staleness=2, n_pods=2, s_xpod=1,
                          wire=True, agg_clocks=2)
faults = wire.make_faults(T, P, seed=5, drop_rate=0.35, max_retries=2)
FORKS = {{
    "churned": (ConsistencyConfig(model="ssp", staleness=2), dict(
        schedule=make_churn(T, P, worker_outages=((1, 0, 2),),
                            drop_inflight=True))),
    "vap-own-unread": (ConsistencyConfig(model="vap", v0=0.5,
                                         read_my_writes=False), {{}}),
    "wired-obs": (wired, dict(obs=ObsSpec())),
    "faulted-churned": (wired.replace(
        window=wire.required_window(wired, faults)), dict(
        faults=faults, schedule=make_churn(
            T, P, n_pods=2, worker_outages=((5, 1, 3),),
            drop_inflight=True))),
}}


def sha(fn, cfg, **kw):
    lowered = fn.lower(fn.init_state(0), cfg, kw.get("schedule"),
                       kw.get("faults"))
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()


out = {{}}
for mesh_name, (data, model) in (("1x1", (1, 1)), ("2x2", (2, 2))):
    mesh = make_ps_mesh(data=data, model=model,
                        devices=jax.devices()[:data * model])
    for name, cfg in DENSE.items():
        fn = PSRuntime(mesh).run_fn(app, cfg, T)
        out[f"{{mesh_name}}-{{name}}"] = sha(fn, cfg)
    for name, (cfg, kw) in FORKS.items():
        fn = PSRuntime(mesh).run_fn(app, cfg, T, **kw)
        out[f"{{mesh_name}}-{{name}}"] = sha(fn, cfg, **kw)
print(json.dumps(out))
"""

SRC = str(Path(__file__).resolve().parents[1] / "src")


def lower_all(src: str = SRC) -> dict:
    """SHA-256 of each program's lowered text, from a child process with
    four CPU devices (this one has one)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", LOWER.format(src=src)],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def hashes():
    return lower_all()


@pytest.mark.parametrize("mesh", ["1x1", "2x2"])
def test_every_fork_of_the_clock_step_lowers(hashes, mesh):
    """The two dense benchmark programs and the churned, VAP without
    read-my-writes, wired, observed and faulted forks all lower, each to
    its own program."""
    names = ("essp3", "bsp", "churned", "vap-own-unread", "wired-obs",
             "faulted-churned")
    got = [hashes[f"{mesh}-{n}"] for n in names]
    assert len(set(got)) == len(names)


if __name__ == "__main__":
    print(json.dumps(lower_all(sys.argv[1] if len(sys.argv) > 1 else SRC),
                     indent=1))
