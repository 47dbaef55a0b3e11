"""utils/simenv.py: the one place CPU-sim child env surgery lives."""

from torch_automatic_distributed_neural_network_tpu.utils.simenv import (
    cpu_sim_env,
)


def test_cpu_sim_env_overrides():
    base = {
        "PYTHONPATH": "/some/real/path",
        "JAX_PLATFORMS": "tpu",
        "XLA_FLAGS": "--xla_foo=1 --xla_force_host_platform_device_count=2",
        "HOME": "/root",
    }
    env = cpu_sim_env(8, base)
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env["PYTHONPATH"] == "/some/real/path"
    assert "--xla_force_host_platform_device_count=8" in env["XLA_FLAGS"]
    assert env["XLA_FLAGS"].count("device_count") == 1
    assert "--xla_foo=1" in env["XLA_FLAGS"]  # unrelated flags kept
    assert env["HOME"] == "/root"
    assert base["JAX_PLATFORMS"] == "tpu"  # the caller's mapping is copied


def test_cpu_sim_env_extra_pythonpath_and_empty():
    env = cpu_sim_env(4, {"PYTHONPATH": "/a"}, extra_pythonpath=("/repo",))
    assert env["PYTHONPATH"].split(":") == ["/repo", "/a"]
    env2 = cpu_sim_env(4, {"PYTHONPATH": ""})
    assert "PYTHONPATH" not in env2  # nothing to carry -> var dropped
