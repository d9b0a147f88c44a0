"""Sizing rehearsal: compile a cell's programs for a described TPU and print
each one's ``memory_analysis()`` per chip, without a chip.

    JAX_PLATFORMS=cpu python3 chipbench/sizing.py --workload <cell> --rows 32768 65536

For each user count it compiles the data generator, the runtime's segment
(the program the window drives, state laid out over the cell's mesh) and
the plain reference, for one v5e chip or the ``v5e:2x2`` host the cell asks
for.  Nothing runs, so the numbers say what fits, not how fast it is.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def _gib(nbytes) -> str:
    return f"{nbytes / 2**30:.2f} GiB"


def report(name: str, compiled) -> dict:
    m = compiled.memory_analysis()
    row = dict(args=m.argument_size_in_bytes, out=m.output_size_in_bytes,
               alias=m.alias_size_in_bytes, temp=m.temp_size_in_bytes)
    row["total"] = row["args"] + row["out"] - row["alias"] + row["temp"]
    print(f"{name}: " + " ".join(f"{k}={_gib(v)}" for k, v in row.items()),
          flush=True)
    return row


def main(argv=None) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rows", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P_

    from repro.apps.matfact import MFConfig, make_mf_app
    from repro.kernels import ops
    from repro.psrun.runtime import PSState, make_run_fn

    from chipbench import run
    from chipbench.apps import matfact as mfa

    jax.config.update("jax_enable_compilation_cache", False)
    ops.set_backend("pallas")       # this host's CPU would pick the jnp path
    cell = run.load_cell(args.workload)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    shape = cell.config["mesh"]
    devs = np.asarray(topo.devices[:shape["data"] * shape["model"]])
    mesh = Mesh(devs.reshape(shape["data"], shape["model"]),
                ("data", "model"))
    one = NamedSharding(Mesh(devs[:1].reshape(1, 1), ("data", "model")),
                        P_())
    cons = run.consistency(cell.traffic)
    K, W = cell.traffic["segment_clocks"], cell.traffic["window"]
    for rows in args.rows:
        cfg = dict(cell.config, n_rows=rows)
        print(f"--- {args.workload} n_rows={rows} on {devs.size} chip(s)")
        made = {}

        def gen(s, cfg=cfg, made=made):
            app = make_mf_app(MFConfig(**{k: cfg[k] for k in mfa.MF_KEYS},
                                       seed=s))
            made["app"] = app
            return app.x0, app.local0

        seed = jax.ShapeDtypeStruct((), jnp.uint32, sharding=one)
        report("data generator", jax.jit(gen).lower(seed).compile())
        x0, local0 = jax.eval_shape(gen, seed)
        app = dataclasses.replace(made["app"], x0=x0, local0=local0)
        g = mfa.dims(cfg)
        dpad = -(-g["d"] // shape["model"]) * shape["model"]

        def arg(shp, dtype, spec):
            return jax.ShapeDtypeStruct(shp, dtype,
                                        sharding=NamedSharding(mesh, spec))

        P = g["P"]
        state = PSState(
            clock=arg((), jnp.int32, P_()),
            base=arg((dpad,), jnp.float32, P_("model")),
            uring=arg((W, P, dpad), jnp.float32, P_(None, None, "model")),
            uclock=arg((W,), jnp.int32, P_()),
            cview=arg((P, P), jnp.int32, P_("data", None)),
            local=jax.tree.map(lambda a: arg(a.shape, a.dtype, P_("data")),
                               local0),
            rng=arg((2,), jnp.uint32, P_()), comm=None)
        fn = make_run_fn(app, cons, K, mesh=mesh)
        report(f"segment of {K} clocks (per chip)",
               jax.jit(lambda st: fn.run_from(st, cons)).lower(state)
               .compile())
        ref = mfa._reference_run(cfg, cell.traffic, run.SETUP_SEGMENTS * K,
                                 jnp.float32, None)
        n_obs = g["n_obs"]
        report("reference", ref.lower(
            jax.ShapeDtypeStruct((g["d"],), jnp.float32, sharding=one),
            jax.ShapeDtypeStruct((P, n_obs), jnp.int32, sharding=one),
            jax.ShapeDtypeStruct((P, n_obs), jnp.int32, sharding=one),
            jax.ShapeDtypeStruct((P, n_obs), jnp.float32, sharding=one),
            seed).compile())
    return 0


if __name__ == "__main__":
    sys.exit(main())
