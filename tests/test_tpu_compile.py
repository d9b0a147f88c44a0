"""Compile rehearsals: the main path's Pallas kernels, compiled by the TPU
compiler for a v5e chip that is described, not attached.

Interpret mode cannot see what Mosaic (TPU's Pallas compiler) refuses:
block shapes off the (8, 128) tiling, too much fast memory, an
unpartitionable kernel.  These tests compile each kernel at real widths —
the PS kernels at the width of MF at rank 100 over 16,384 users and
17,770 items and at the cells' unpadded ring widths, MF's dense objective
over 32,768 users, flash attention at
Qwen3-0.6B's widths — and assert that the
program holds the kernel (``tpu_custom_call``) under its name.  The kernels are called
with ``interpret=False`` directly, because ``ops.get_backend()`` sees the
CPU here.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and the file's tests must
collect alike in every test worker.
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import delta_pack as dp
from repro.kernels import flash_attention as fa
from repro.kernels import mf_sse, ops, ps_view

# MF at rank 100 x (16,384 users + 17,770 items), padded to 128 lanes.
D_MF = 3_415_424
W_RING, P_WORKERS = 5, 8            # essp(3) ring window, 8 workers
# The cells' rings (W, P, readers, d), unpadded: ESSP(3) and BSP over MF at
# rank 100 x (32,768 users + 17,770 items) on one chip; ESSP(3) over
# 131,072 users split over a mesh axis of 2, 4 readers a data shard.
CELL_RINGS = [(5, 8, 8, 5_053_800), (2, 8, 8, 5_053_800),
              (5, 8, 4, 7_442_100)]
# MF's dense objective: 32,768 users x 17,770 items (padded to 128 lanes)
N_MF, M_PAD_MF, K_MF = 32_768, 17_792, 100
QWEN3 = dict(B=4, S=512, H=16, Hkv=8, Dh=128)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip can be written to the persistent
    # cache but never read back here: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(name, fn, *args):
    """The compiled program holds the kernel, under its ``name=`` (inside
    the transformations applied to it: ``jvp(flash_attention)``)."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    kernel = re.compile(rf"(\w+\()*{name}\)*")
    assert any(kernel.fullmatch(part)
               for op_name in re.findall(r'op_name="([^"]*)"', text)
               for part in op_name.split("/"))


@pytest.mark.parametrize("readers", [P_WORKERS, P_WORKERS // 2])
def test_ring_view_compiles(one_chip, readers):
    """All readers (one worker shard) and half of them (two data shards)."""
    _assert_kernel(
        "ring_view",
        lambda b, u, uc, cv: ps_view.ring_view(b, u, uc, cv, interpret=False),
        _shape(one_chip, (D_MF,)),
        _shape(one_chip, (W_RING, P_WORKERS, D_MF)),
        _shape(one_chip, (W_RING,), jnp.int32),
        _shape(one_chip, (readers, P_WORKERS), jnp.int32))


def test_vap_suffix_norms_compiles(one_chip):
    _assert_kernel(
        "vap_suffix_norms",
        lambda u, uc, c: ps_view.vap_suffix_norms(u, uc, c, interpret=False),
        _shape(one_chip, (W_RING, P_WORKERS, D_MF)),
        _shape(one_chip, (W_RING,), jnp.int32),
        _shape(one_chip, (), jnp.int32))


@pytest.mark.parametrize(("W", "P", "R", "d"), CELL_RINGS)
def test_ring_view_compiles_at_cell_width(one_chip, W, P, R, d):
    """The ring at its unpadded width, in `block_lanes`' blocks."""
    _assert_kernel(
        "ring_view",
        lambda b, u, uc, cv: ps_view.ring_view(b, u, uc, cv, interpret=False),
        _shape(one_chip, (d,)),
        _shape(one_chip, (W, P, d)),
        _shape(one_chip, (W,), jnp.int32),
        _shape(one_chip, (R, P), jnp.int32))


@pytest.mark.parametrize(("W", "P", "R", "d"), CELL_RINGS)
def test_vap_suffix_norms_compiles_at_cell_width(one_chip, W, P, R, d):
    _assert_kernel(
        "vap_suffix_norms",
        lambda u, uc, c: ps_view.vap_suffix_norms(u, uc, c, interpret=False),
        _shape(one_chip, (W, P, d)),
        _shape(one_chip, (W,), jnp.int32),
        _shape(one_chip, (), jnp.int32))


@pytest.mark.parametrize("quant", ["f32", "int8"])
def test_delta_pack_compiles(one_chip, quant):
    _assert_kernel(
        "delta_pack",
        lambda d, t, s: dp.delta_pack(d, t, s, quant, interpret=False),
        _shape(one_chip, (P_WORKERS, D_MF)),
        _shape(one_chip, (P_WORKERS,)),
        _shape(one_chip, (P_WORKERS,)))


def test_mf_sse_compiles(one_chip):
    """MF's dense-block objective at the Netflix cell's widths: ragged
    column blocks, k = 100 unpadded, the int8 count block."""
    _assert_kernel(
        "mf_sse",
        lambda L, R, V, C: mf_sse.mf_sse(L, R, V, C, interpret=False),
        _shape(one_chip, (N_MF, K_MF)),
        _shape(one_chip, (K_MF, M_PAD_MF)),
        _shape(one_chip, (N_MF, M_PAD_MF)),
        _shape(one_chip, (N_MF, M_PAD_MF), jnp.int8))


def _attention_args(one_chip):
    B, S, H, Hkv, Dh = (QWEN3[k] for k in ("B", "S", "H", "Hkv", "Dh"))
    return (_shape(one_chip, (B, S, H, Dh), jnp.bfloat16),
            _shape(one_chip, (B, S, Hkv, Dh), jnp.bfloat16),
            _shape(one_chip, (B, S, Hkv, Dh), jnp.bfloat16),
            _shape(one_chip, (B, S), jnp.int32))


def test_flash_attention_forward_compiles(one_chip):
    scale = QWEN3["Dh"] ** -0.5
    _assert_kernel(
        "flash_attention",
        lambda q, k, v, pos: fa.flash_attention(
            q, k, v, scale=scale, q_pos=pos, kv_pos=pos, interpret=False),
        *_attention_args(one_chip))


def test_flash_attention_grad_compiles(one_chip):
    """The gradient goes through `ops.attention`'s custom VJP: the Pallas
    forward stays in the program, the backward is the reference's VJP.
    As in a train step the loss value is kept (a bare ``grad`` would let
    the compiler drop the forward kernel, whose output it never reads)."""
    scale = QWEN3["Dh"] ** -0.5

    def loss(q, k, v, pos):
        out = ops.attention(q, k, v, scale=scale, q_pos=pos, kv_pos=pos)
        return jnp.sum(out.astype(jnp.float32))

    ops.set_backend("pallas")
    try:
        _assert_kernel("flash_attention",
                       jax.value_and_grad(loss, argnums=(0, 1, 2)),
                       *_attention_args(one_chip))
    finally:
        ops.set_backend("auto")
