"""Topology / mesh construction tests (component C10)."""

import jax
import numpy as np
import pytest

import torch_automatic_distributed_neural_network_tpu as tad
from torch_automatic_distributed_neural_network_tpu import topology


def test_detect(devices8):
    topo = topology.detect()
    assert topo.num_devices == 8
    assert topo.platform == "cpu"
    assert not topo.is_multihost


def test_default_mesh_is_pure_dp(devices8):
    mesh = tad.build_mesh()
    d = tad.mesh_degrees(mesh)
    assert d["data"] == 8
    assert all(v == 1 for k, v in d.items() if k != "data")


def test_mesh_axes_inference(devices8):
    mesh = tad.build_mesh(tensor=2, fsdp=-1)
    d = tad.mesh_degrees(mesh)
    assert d["tensor"] == 2 and d["fsdp"] == 4


def test_mesh_explicit_product_must_divide(devices8):
    with pytest.raises(ValueError):
        tad.build_mesh(tensor=3)


def test_mesh_auto_expand_data(devices8):
    # specifying only tensor=2 absorbs the rest into data
    mesh = tad.build_mesh(tensor=2)
    d = tad.mesh_degrees(mesh)
    assert d["tensor"] == 2 and d["data"] == 4


def test_two_infer_axes_rejected(devices8):
    with pytest.raises(ValueError):
        tad.build_mesh(tensor=-1, fsdp=-1)


def test_single_device_mesh():
    mesh = tad.single_device_mesh()
    assert mesh.devices.size == 1
    assert mesh.axis_names == topology.MESH_AXES


def test_mesh_covers_all_devices(devices8):
    mesh = tad.build_mesh(data=2, fsdp=2, tensor=2)
    assert sorted(d.id for d in mesh.devices.flatten()) == sorted(
        d.id for d in jax.devices()
    )


# --- hybrid ICI x DCN factorization (SURVEY.md §5 comm row) ----------------

def _shapes(degrees, num_slices):
    fact = topology.hybrid_factorization(degrees, num_slices)
    if fact is None:
        return None
    ici, dcn = fact
    return dict(zip(topology.MESH_AXES, ici)), dict(zip(topology.MESH_AXES, dcn))


def test_hybrid_single_dcn_axis():
    # 2 slices x 4 chips: data=8 splits into 2 across DCN x 4 in-slice
    ici, dcn = _shapes({"data": 8}, 2)
    assert dcn["data"] == 2 and ici["data"] == 4
    assert all(v == 1 for k, v in dcn.items() if k != "data")


def test_hybrid_pipe_takes_priority():
    # 4 slices x 2 chips: pipe=4 spans DCN, tensor stays in-slice
    ici, dcn = _shapes({"pipe": 4, "tensor": 2}, 4)
    assert dcn["pipe"] == 4 and ici["pipe"] == 1
    assert dcn["tensor"] == 1 and ici["tensor"] == 2


def test_hybrid_two_axes_span_dcn():
    # 4 slices: pipe=2 and data=2 EACH take one DCN factor (the round-2
    # code could only put ONE axis across DCN and fell through here)
    ici, dcn = _shapes({"pipe": 2, "data": 4, "tensor": 2}, 4)
    assert dcn["pipe"] == 2 and dcn["data"] == 2
    assert ici["pipe"] == 1 and ici["data"] == 2 and ici["tensor"] == 2


def test_hybrid_partial_axis_split():
    # 2 slices: data=4 -> 2 across DCN, 2 within each slice
    ici, dcn = _shapes({"data": 4, "fsdp": 2}, 2)
    assert dcn["data"] == 2 and ici["data"] == 2
    assert dcn["fsdp"] == 1 and ici["fsdp"] == 2


def test_hybrid_ici_axes_never_cross_slices():
    # tensor=8 over 2 slices has no DCN-tolerant degree to span them
    assert topology.hybrid_factorization({"tensor": 8}, 2) is None


def test_hybrid_insufficient_dcn_degree():
    # pipe*data = 4 cannot cover 8 slices
    assert topology.hybrid_factorization({"pipe": 2, "data": 2}, 8) is None


@pytest.mark.parametrize("slices,per_slice,axes", [
    (2, 4, {"data": 8}),
    (4, 2, {"pipe": 4, "tensor": 2}),
    (2, 4, {"pipe": 2, "data": 2, "tensor": 2}),
])
def test_build_mesh_hybrid_wiring(devices8, monkeypatch, slices, per_slice, axes):
    """build_mesh on a (simulated) multi-slice topology must route through
    create_hybrid_device_mesh with the factorized shapes.  slice_index is
    faked on the CPU-sim devices via detect(); the jax mesh_utils call is
    recorded and stubbed (its internals are upstream-tested)."""
    captured = {}

    def fake_hybrid(ici_shape, dcn_shape, devices=None, **kw):
        captured["ici"] = list(ici_shape)
        captured["dcn"] = list(dcn_shape)
        full = [i * d for i, d in zip(ici_shape, dcn_shape)]
        return np.asarray(devices).reshape(full)

    monkeypatch.setattr(
        topology.mesh_utils, "create_hybrid_device_mesh", fake_hybrid
    )
    monkeypatch.setattr(
        topology, "detect",
        lambda devices=None: topology.Topology(
            num_devices=8, num_hosts=slices, platform="cpu",
            device_kind="cpu", num_slices=slices,
            devices_per_slice=per_slice,
        ),
    )
    mesh = tad.build_mesh(**axes)
    assert captured, "hybrid path was not taken"
    import math
    assert math.prod(captured["dcn"]) == slices
    assert math.prod(captured["ici"]) == per_slice
    got = tad.mesh_degrees(mesh)
    for ax, d in axes.items():
        assert got[ax] == d


def test_build_mesh_hybrid_fallthrough_warns(devices8, monkeypatch):
    """When the DCN-tolerant degrees cannot cover the slice count the
    fall-through to a flat mesh must be LOUD (round-2 weak #3: it was
    silent)."""
    monkeypatch.setattr(
        topology, "detect",
        lambda devices=None: topology.Topology(
            num_devices=8, num_hosts=2, platform="cpu", device_kind="cpu",
            num_slices=2, devices_per_slice=4,
        ),
    )
    with pytest.warns(UserWarning, match="FLAT device mesh"):
        mesh = tad.build_mesh(tensor=8)
    assert tad.mesh_degrees(mesh)["tensor"] == 8


# -- SKU parsing (what-if sweeps) ---------------------------------------------


def test_parse_topology_v5p_1024():
    topo = topology.parse_topology("v5p-1024")
    assert topo.num_devices == 1024
    assert topo.num_hosts == 256  # 4 chips per host
    assert topo.device_kind == "v5p" and topo.platform == "tpu"
    assert topo.num_slices == 1
    assert topo.chip is topology._CHIP_SPECS["v5p"]


def test_parse_topology_multislice():
    topo = topology.parse_topology("v5e-256x4")
    assert topo.num_devices == 1024 and topo.num_slices == 4
    assert topo.devices_per_slice == 256
    assert topo.is_multislice


def test_parse_topology_rejects_unknown_sku():
    with pytest.raises(ValueError, match="unknown TPU SKU"):
        topology.parse_topology("v9z-16")
    with pytest.raises(ValueError, match="cannot parse topology"):
        topology.parse_topology("v5p")
    with pytest.raises(ValueError, match=">= 1 chip"):
        topology.parse_topology("v5p-0")


def test_parse_topology_dcn_override_changes_chip_and_fingerprint():
    from torch_automatic_distributed_neural_network_tpu.tune import (
        cache as tune_cache,
    )

    base = topology.parse_topology("v5p-64")
    slow = topology.parse_topology("v5p-64", dcn_bytes_per_s=1e9,
                                   dcn_latency_s=1e-3)
    assert base.chip_override is None
    assert slow.chip_override is not None
    assert slow.chip.dcn_bytes_per_s == 1e9
    assert slow.chip.dcn_latency_s == 1e-3
    # everything but DCN comes from the stock SKU
    assert slow.chip.flops_per_s == base.chip.flops_per_s
    assert (tune_cache.topology_fingerprint(base)
            != tune_cache.topology_fingerprint(slow))


# -- the one peak table -------------------------------------------------------


def test_chip_table_resolves_the_v5e_device_kind():
    from torch_automatic_distributed_neural_network_tpu import planner
    from torch_automatic_distributed_neural_network_tpu.training import (
        peak_flops_per_chip,
    )

    # "TPU v5 lite" is what jax.devices()[0].device_kind says on a v5e
    spec = topology.chip_spec("TPU v5 lite")
    assert spec is topology._CHIP_SPECS["v5 lite"]
    assert spec.flops_per_s == 197e12 and spec.hbm_bytes == 16 * 2**30
    # MFU reporting and the planner's HBM budget read the same table
    assert peak_flops_per_chip("TPU v5 lite") == spec.flops_per_s
    assert planner._hbm_bytes("TPU v5 lite") == spec.hbm_bytes
    assert peak_flops_per_chip() == topology.chip_spec("cpu").flops_per_s


@pytest.mark.parametrize("kind", ["unknown", "Quantum 9000", "TPU v3"])
def test_unknown_device_kind_is_an_error_not_a_default(kind):
    from torch_automatic_distributed_neural_network_tpu import planner
    from torch_automatic_distributed_neural_network_tpu.training import (
        peak_flops_per_chip,
    )

    with pytest.raises(ValueError, match="no peak numbers"):
        topology.chip_spec(kind)
    with pytest.raises(ValueError, match="no peak numbers"):
        peak_flops_per_chip(kind)
    with pytest.raises(ValueError, match="no peak numbers"):
        planner._hbm_bytes(kind)
    with pytest.raises(ValueError, match="no peak numbers"):
        topology.Topology(num_devices=1, num_hosts=1, platform="tpu",
                          device_kind=kind).chip


# -- compile cache placement --------------------------------------------------


@pytest.fixture
def cache_config():
    """Put jax's compile-cache config back as the test found it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = (jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs)
    yield
    jax.config.update("jax_compilation_cache_dir", prev[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", prev[1])
    cc.reset_cache()


def test_compile_cache_placed_by_the_environment(
        tmp_path, monkeypatch, cache_config):
    placed = tmp_path / "placed" / "cache"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(placed))
    monkeypatch.delenv("TADNN_NO_COMPILE_CACHE", raising=False)
    monkeypatch.setattr(topology, "_DEFAULT_COMPILE_CACHE",
                        str(tmp_path / "default"))
    before = jax.config.jax_compilation_cache_dir
    assert topology.compilation_cache_dir() == (str(placed), "env")
    assert topology.enable_compilation_cache() == str(placed)
    # the directory is left to the variable (jax reads it itself) ...
    assert jax.config.jax_compilation_cache_dir == before
    # ... only the threshold is set, and nothing is created
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 1.0
    assert list(tmp_path.iterdir()) == []


def test_compile_cache_default_is_one_fixed_path_in_the_checkout(
        tmp_path, monkeypatch):
    import pathlib

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = pathlib.Path(topology.__file__).resolve().parents[1]
    seen = []
    for cwd in (tmp_path, repo / "tests"):
        monkeypatch.chdir(cwd)
        seen.append(topology.compilation_cache_dir())
    assert seen[0] == seen[1] == (str(repo / ".jax_cache"), "default")
    # listed in .gitignore, so a checkout never commits it
    assert ".jax_cache/" in (repo / ".gitignore").read_text().split()


def test_compile_cache_default_is_created_only_when_called(
        tmp_path, monkeypatch, cache_config):
    default = tmp_path / "jc"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("TADNN_NO_COMPILE_CACHE", raising=False)
    monkeypatch.setattr(topology, "_DEFAULT_COMPILE_CACHE", str(default))
    assert not default.exists()
    assert topology.enable_compilation_cache() == str(default)
    assert default.is_dir()
    assert jax.config.jax_compilation_cache_dir == str(default)
    assert topology.enable_compilation_cache() == str(default)  # idempotent


def test_compile_cache_opt_out(tmp_path, monkeypatch):
    monkeypatch.setenv("TADNN_NO_COMPILE_CACHE", "1")
    monkeypatch.setattr(topology, "_DEFAULT_COMPILE_CACHE",
                        str(tmp_path / "jc"))
    before = jax.config.jax_compilation_cache_dir
    assert topology.enable_compilation_cache() is None
    assert jax.config.jax_compilation_cache_dir == before
    assert list(tmp_path.iterdir()) == []
