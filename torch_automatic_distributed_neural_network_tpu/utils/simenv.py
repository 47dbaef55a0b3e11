"""CPU-sim subprocess environment builder.

One place for the env surgery every CPU-sim child process needs (the
host-simulating launcher's workers, the real multi-process test).
Stdlib only.
"""

from __future__ import annotations

import os


def cpu_sim_env(
    n_devices: int,
    base: dict | None = None,
    *,
    extra_pythonpath: tuple[str, ...] = (),
) -> dict:
    """Environment for a child process on ``n_devices`` simulated CPU
    devices: force JAX_PLATFORMS=cpu and set the virtual device count in
    XLA_FLAGS (replacing any existing count flag).  ``extra_pythonpath``
    entries are prepended (e.g. the repo root for test workers)."""
    env = dict(os.environ if base is None else base)
    paths = [
        p for p in (
            *extra_pythonpath,
            *env.get("PYTHONPATH", "").split(os.pathsep),
        ) if p
    ]
    if paths:
        env["PYTHONPATH"] = os.pathsep.join(paths)
    else:
        env.pop("PYTHONPATH", None)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [
        f for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    ]
    env["XLA_FLAGS"] = " ".join(
        flags + [f"--xla_force_host_platform_device_count={n_devices}"]
    )
    return env
