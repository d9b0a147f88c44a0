"""Sharding-rule logic (mesh-free parts + small fake meshes)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs import get_config
from repro.launch import sharding as shd
from repro.models.params import spec, shardings


@pytest.fixture(scope="module")
def mesh1():
    # single-device mesh with both axis names (size 1 each)
    dev = np.array(jax.devices()[:1]).reshape(1, 1)
    return Mesh(dev, ("data", "model"))


def test_ns_drops_non_dividing_axes(mesh1):
    s = shd.ns(mesh1, (7, 8), "data", "model")
    # axes of size 1 always divide; spec keeps them
    assert s.spec == P("data", "model")


def test_ns_skips_missing_axes(mesh1):
    s = shd.ns(mesh1, (8, 8), ("pod", "data"), None)
    assert s.spec == P("data", None)   # no "pod" axis on this mesh


def test_ns_no_axis_reuse(mesh1):
    s = shd.ns(mesh1, (8, 8), "model", "model")
    assert s.spec == P("model", None)  # second use dropped


def test_param_rules_profiles():
    tp = shd.param_rules("tp")
    fsdp = shd.param_rules("tp_fsdp")
    assert tp["embed"] is None
    assert fsdp["embed"] == shd.DATA_AXES
    assert tp["heads"] == "model"
    assert tp["experts"] == "model"


def test_profile_selection():
    assert shd.profile_for(get_config("jamba-1.5-large-398b")) == "tp_fsdp"
    assert shd.profile_for(get_config("llama3-8b")) == "tp"
    assert shd.profile_for(get_config("qwen3-0.6b")) == "tp"


def test_activation_rules_sp_toggle():
    from repro.configs.base import INPUT_SHAPES
    train = shd.activation_rules(INPUT_SHAPES["train_4k"])
    dec = shd.activation_rules(INPUT_SHAPES["decode_32k"])
    assert train["seq_res"] == "model"      # sequence parallelism on
    assert dec["seq_res"] is None           # decode: seq=1


def test_param_shardings_tree(mesh1):
    specs = {"w": spec((8, 16), ("embed", "mlp")),
             "e": spec((32, 8), ("vocab", "embed"))}
    tree = shardings(specs, mesh1, shd.param_rules("tp"))
    assert tree["w"].spec == P(None, "model")
    assert tree["e"].spec == P("model", None)


def test_roofline_row_math():
    from benchmarks.roofline import roofline_row
    art = {
        "arch": "llama3-8b", "shape": "train_4k", "mesh": "16x16",
        "chips": 256, "kind": "train",
        "flops_per_device": 197e12,           # exactly 1s of compute
        "bytes_accessed_per_device": 819e9,   # exactly 1s of HBM
        "collectives": {"total_bytes": 150e9, "count_by_op": {}},
        "memory": {"total_bytes": 8 * 2**30},
    }
    r = roofline_row(art)
    assert r["compute_s"] == pytest.approx(1.0)
    assert r["memory_s"] == pytest.approx(1.0)
    assert r["collective_s"] == pytest.approx(1.0)
    assert r["fits_hbm"]
    # llama3-8b train_4k model flops: 6 * ~8.03B * 1.048M tokens ~ 5.05e16
    assert 4.8e16 < r["model_flops"] < 5.4e16


def test_chip_peaks_unknown_kind_raises():
    from repro.launch.mesh import chip_peaks
    assert chip_peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        chip_peaks("TPU v9 imaginary")


@pytest.mark.parametrize("env", [None, "/elsewhere/jax-cache"])
def test_compile_cache_placement(monkeypatch, env):
    """$JAX_COMPILATION_CACHE_DIR wins and nothing is set in code;
    otherwise the cache sits at the fixed, git-ignored <repo>/.jax_cache."""
    from repro.launch import cache
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    was = jax.config.jax_compilation_cache_dir
    try:
        got = cache.enable_compile_cache()
        now = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    if env is None:
        assert got == now == str(cache.REPO_ROOT / ".jax_cache")
        assert (cache.REPO_ROOT / "chip_smoke.py").exists()
        assert ".jax_cache/" in (cache.REPO_ROOT / ".gitignore").read_text()
    else:
        assert got == env and now == was


def test_active_params_moe():
    from benchmarks.roofline import active_params
    full = active_params("llama3-8b")
    assert full == pytest.approx(8.03e9, rel=0.05)
    act = active_params("qwen3-moe-30b-a3b")
    total = active_params("qwen3-0.6b")  # sanity: returns floats
    assert 2e9 < act < 4.5e9             # ~3B active of 30B total
    assert act < 0.2 * 30e9
