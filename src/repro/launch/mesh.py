"""Mesh construction — the single factory module for every device mesh.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — required because the dry-run (and the CI
forced-multi-device lane) must set ``XLA_FLAGS`` before any jax
initialization.  All mesh construction in the repo routes through here so a
``--xla_force_host_platform_device_count=N`` override is honored everywhere:
``core.sweep`` takes its 1-D batch mesh from :func:`make_batch_mesh`, and
the executable runtime (``repro.psrun``) takes its 2-D worker × shard mesh
from :func:`make_ps_mesh`.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e production mesh: one pod = 16x16 = 256 chips
    ("data","model"); two pods = 512 chips with a leading "pod" axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh(model: int | None = None):
    """Degenerate mesh over the locally available devices (CPU tests)."""
    n = len(jax.devices())
    model = model or 1
    assert n % model == 0
    return jax.make_mesh((n // model, model), ("data", "model"))


def make_batch_mesh(devices=None) -> Mesh:
    """1-D ``("batch",)`` mesh for embarrassingly parallel sweeps.

    ``core.sweep`` shards its flattened (config × seed) batch over this;
    defaults to every locally visible device.
    """
    if devices is None:
        devices = jax.devices()
    return Mesh(np.asarray(devices), ("batch",))


def make_pods_mesh(pods: int | None = None, data: int | None = None,
                   model: int | None = None, devices=None) -> Mesh:
    """3-D ``("pod","data","model")`` mesh for the hierarchical runtime.

    The leading "pod" axis carries parameter-shard *replicas* (one full
    copy of the table per pod); within a pod, "data" carries that pod's PS
    workers and "model" its parameter shards — `repro.pods` partitions the
    ``P`` workers pod-major over ``("pod","data")``, matching
    ``core.delays.pod_of``.  Defaults: 2 pods when the device count allows
    (else 1), then the `make_ps_mesh` policy for the within-pod axes
    (model=2 when even, >=1 worker-pair per data shard being the caller's
    job).  The CI pods lane forces 16 host devices and runs 2x4x2.
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if pods is None:
        pods = 2 if n % 2 == 0 and n >= 4 else 1
    if n % pods:
        raise ValueError(f"pods={pods} does not divide the {n} visible "
                         f"devices")
    per_pod = n // pods
    if model is None:
        if data is not None:
            if per_pod % data:
                raise ValueError(
                    f"data={data} does not divide the per-pod device count "
                    f"({per_pod}); pass model= explicitly")
            model = per_pod // data
        else:
            model = 2 if (per_pod > 1 and per_pod % 2 == 0) else 1
    if data is None:
        if per_pod % model:
            raise ValueError(
                f"model={model} does not divide the per-pod device count "
                f"({per_pod}); pass data= explicitly")
        data = per_pod // model
    if pods * data * model > n:
        raise ValueError(f"mesh ({pods}x{data}x{model}) needs "
                         f"{pods * data * model} devices, have {n}")
    return Mesh(np.asarray(devices[:pods * data * model])
                .reshape(pods, data, model), ("pod", "data", "model"))


def make_ps_mesh(data: int | None = None, model: int | None = None,
                 devices=None) -> Mesh:
    """``("data","model")`` mesh for the executable parameter server.

    The "data" axis carries PS *workers* (data partitions), the "model"
    axis carries *parameter shards* (the server side of the table).  By
    default uses every visible device, preferring a true 2-D layout
    (``model=2`` when the device count is even): besides being the layout
    the runtime exists to exercise, it keeps >1 worker per data shard for
    typical worker counts, where the runtime's vmapped worker step compiles
    to the same fused arithmetic as the simulator oracle (a 1-worker shard
    can drift by 1 ulp — see ``psrun.validate``).  Pass ``data`` explicitly
    to run on a device subset (e.g. the worker-scaling curves in
    ``benchmarks/psrun_bench.py``).
    """
    if devices is None:
        devices = jax.devices()
    if model is None:
        if data is not None:
            if len(devices) % data:
                raise ValueError(
                    f"data={data} does not divide the {len(devices)} "
                    f"visible devices; pass model= explicitly")
            model = len(devices) // data
        else:
            model = 2 if (len(devices) > 1 and len(devices) % 2 == 0) else 1
    if data is None:
        if len(devices) % model:
            raise ValueError(
                f"model={model} does not divide the {len(devices)} "
                f"visible devices; pass data= explicitly")
        data = len(devices) // model
    n = data * model
    if n > len(devices):
        raise ValueError(
            f"mesh ({data}x{model}) needs {n} devices, have {len(devices)}")
    return Mesh(np.asarray(devices[:n]).reshape(data, model),
                ("data", "model"))


# Published per-chip peaks (roofline denominators), keyed by JAX's
# ``device_kind``.  Source: Google Cloud documentation, "TPU v5e" (197
# TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI).
# The ICI total is 4 links x 50 GB/s; ``ici_links`` = 3 usable per mesh
# axis on a 2-D torus slice is this repo's assumption, not published.
CHIP_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes": 16e9, "hbm_bw": 819e9,
                    "ici_link_bw": 50e9, "ici_links": 3},
}


def chip_peaks(device_kind: str) -> dict:
    """Peaks of one chip of ``device_kind``; an unknown kind is an error."""
    if device_kind not in CHIP_PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to CHIP_PEAKS")
    return CHIP_PEAKS[device_kind]
