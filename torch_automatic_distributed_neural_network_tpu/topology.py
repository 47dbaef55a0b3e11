"""Topology discovery and device-mesh construction (component C10).

Reference capability (SURVEY.md C10; BASELINE.json north star): the reference
enumerates CUDA devices (``torch.cuda.device_count``) and the TPU-native
version must "learn TPU pod mesh topology (v4/v5 ICI rings)".

TPU-native realization: ``jax.devices()`` + ``mesh_utils.create_device_mesh``
(which is ICI-topology-aware on real TPU slices) and
``create_hybrid_device_mesh`` for multi-slice (ICI x DCN) deployments.

The canonical mesh axes used throughout the framework:

=========  =======================================================
axis       used by
=========  =======================================================
``data``   data parallelism (batch sharding; DDP/bucketed-DDP analog)
``fsdp``   ZeRO-3 parameter/optimizer sharding (can alias ``data``)
``tensor`` Megatron-style tensor parallelism (col/row weight splits)
``seq``    sequence / context parallelism (ring attention, Ulysses)
``pipe``   pipeline parallelism (stage meshes)
``expert`` expert parallelism (MoE all_to_all dispatch)
=========  =======================================================

Axes are ordered slowest-varying first so that axes that carry the most
traffic (``tensor``, ``seq``) land on the fastest (innermost ICI) links,
and ``data`` — which only carries one gradient allreduce per step — can be
placed across DCN on hybrid meshes.
"""

from __future__ import annotations

import dataclasses
import math
import os
import warnings
from typing import Mapping, Sequence

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh

# Canonical axis ordering: outermost (slowest links OK) -> innermost
# (fastest links required).  DCN-friendly axes first.
MESH_AXES: tuple[str, ...] = ("pipe", "data", "fsdp", "expert", "seq", "tensor")

# Axes whose collectives are latency/bandwidth critical and must ride ICI.
ICI_AXES: frozenset[str] = frozenset({"tensor", "seq", "expert", "fsdp"})
# Axes that tolerate DCN (one collective per step, overlappable).
DCN_OK_AXES: tuple[str, ...] = ("pipe", "data")


@dataclasses.dataclass(frozen=True)
class Topology:
    """A snapshot of the accelerator topology visible to this process.

    Also the *hypothetical* fleet handle for the what-if planner
    (:func:`parse_topology`): ``chip_override`` carries a per-sweep
    :class:`ChipSpec` (e.g. a DCN bandwidth/latency variant) so the
    tune/simulate cost models can sweep interconnect assumptions
    without editing the datasheet table.
    """

    num_devices: int
    num_hosts: int
    platform: str  # 'tpu' | 'cpu' | 'gpu'
    device_kind: str
    num_slices: int = 1
    devices_per_slice: int | None = None
    chip_override: "ChipSpec | None" = None

    @property
    def is_multihost(self) -> bool:
        return self.num_hosts > 1

    @property
    def is_multislice(self) -> bool:
        return self.num_slices > 1

    @property
    def chip(self) -> "ChipSpec":
        """Per-chip peak numbers for this topology's device kind."""
        if self.chip_override is not None:
            return self.chip_override
        return chip_spec(self.device_kind)


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    """Per-chip peak numbers: the one table MFU reporting, the planner's
    HBM budget and the tune/ cost model all read.

    Bandwidths are bytes/s per chip (one direction); ``ici`` is the
    intra-slice interconnect, ``dcn`` the data-center network between
    slices/hosts.  Latencies are per-hop.  The cost model only needs
    relative magnitudes to *rank* candidate plans, and
    ``tune/measure.py`` exists for the cases where ranking by these
    numbers isn't enough.
    """

    flops_per_s: float  # peak dense bf16 matmul
    hbm_bytes: int  # capacity
    hbm_bytes_per_s: float
    ici_bytes_per_s: float
    dcn_bytes_per_s: float
    ici_latency_s: float = 1e-6
    dcn_latency_s: float = 25e-6


# Keyed by ``device_kind`` substring (``jax.devices()[0].device_kind`` is
# "TPU v5 lite" on a v5e; the short SKU spellings serve parse_topology).
# Source: Google Cloud TPU documentation, the per-generation system
# architecture pages ("TPU v5e": 197 TFLOP/s bf16, 16 GB HBM2e at
# 819 GB/s, 1,600 Gbit/s ICI per chip; likewise v4, v5p, v6e).  The
# 'cpu' entry is not a datasheet: it models the host-platform sim (tiny
# compute, shared-memory "links") so plan ranking stays sane in CI.
_CHIP_SPECS: dict[str, ChipSpec] = {
    "v5 lite": ChipSpec(197e12, 16 * 2**30, 8.2e11, 1.86e11, 6.25e9),
    "v5e": ChipSpec(197e12, 16 * 2**30, 8.2e11, 1.86e11, 6.25e9),
    "v5p": ChipSpec(459e12, 95 * 2**30, 2.77e12, 4.8e11, 6.25e9),
    "v4": ChipSpec(275e12, 32 * 2**30, 1.23e12, 3.0e11, 6.25e9),
    "v6": ChipSpec(918e12, 32 * 2**30, 1.64e12, 3.58e11, 6.25e9),
    "cpu": ChipSpec(5e10, 8 * 2**30, 2e10, 1e9, 1e8,
                    ici_latency_s=5e-6, dcn_latency_s=100e-6),
}


def chip_spec(device_kind: str) -> ChipSpec:
    """Look up :class:`ChipSpec` by device-kind substring.  A kind the
    table does not hold is an error, not a default: an MFU or a plan
    priced against a made-up chip reads like a measurement."""
    dk = device_kind.lower()
    for k, v in _CHIP_SPECS.items():
        if k in dk:
            return v
    raise ValueError(
        f"no peak numbers for device kind {device_kind!r} — known kinds: "
        f"{sorted(_CHIP_SPECS)}; add it to topology._CHIP_SPECS with its "
        f"source")


# Chips per host for hypothetical fleets: TPU hosts carry 4 chips
# (v4/v5/v6 boards); the CPU "fleet" is the 8-device host-platform sim.
_CHIPS_PER_HOST = {"cpu": 8}
_DEFAULT_CHIPS_PER_HOST = 4


def parse_topology(
    spec: str,
    *,
    dcn_bytes_per_s: float | None = None,
    dcn_latency_s: float | None = None,
) -> Topology:
    """A hypothetical :class:`Topology` from a TPU-SKU spelling.

    ``"v5p-1024"`` is a single-slice 1024-chip fleet;
    ``"v5e-256x4"`` is 4 slices of 256 chips joined by DCN.  The kind
    must name a known :data:`_CHIP_SPECS` entry EXACTLY — a typo'd SKU
    must fail the sweep loudly, not price a fantasy fleet.

    ``dcn_bytes_per_s`` / ``dcn_latency_s`` override the datasheet DCN
    numbers (stored as ``chip_override``), which is how ``tadnn
    simulate`` sweeps inter-slice interconnect assumptions.
    """
    text = str(spec).strip().lower()
    kind, sep, shape = text.partition("-")
    if not sep or not shape:
        raise ValueError(
            f"cannot parse topology {spec!r} — expected '<kind>-<chips>' "
            f"or '<kind>-<chips_per_slice>x<slices>' (e.g. 'v5p-1024', "
            f"'v5e-256x4')")
    if kind not in _CHIP_SPECS:
        raise ValueError(
            f"unknown TPU SKU {kind!r} in topology {spec!r} — known "
            f"kinds: {sorted(_CHIP_SPECS)}")
    per_slice_s, x, slices_s = shape.partition("x")
    try:
        per_slice = int(per_slice_s)
        num_slices = int(slices_s) if x else 1
    except ValueError:
        raise ValueError(
            f"cannot parse topology {spec!r}: {shape!r} is not "
            f"'<chips>' or '<chips_per_slice>x<slices>'") from None
    if per_slice < 1 or num_slices < 1:
        raise ValueError(
            f"topology {spec!r} needs >= 1 chip per slice and >= 1 "
            f"slice, got {per_slice}x{num_slices}")
    num_devices = per_slice * num_slices
    chip = _CHIP_SPECS[kind]
    override = None
    if dcn_bytes_per_s is not None or dcn_latency_s is not None:
        override = dataclasses.replace(
            chip,
            dcn_bytes_per_s=(chip.dcn_bytes_per_s
                             if dcn_bytes_per_s is None
                             else float(dcn_bytes_per_s)),
            dcn_latency_s=(chip.dcn_latency_s if dcn_latency_s is None
                           else float(dcn_latency_s)),
        )
    per_host = _CHIPS_PER_HOST.get(kind, _DEFAULT_CHIPS_PER_HOST)
    return Topology(
        num_devices=num_devices,
        num_hosts=max(1, num_devices // per_host),
        platform="cpu" if kind == "cpu" else "tpu",
        device_kind=kind,
        num_slices=num_slices,
        devices_per_slice=per_slice,
        chip_override=override,
    )


_SIZE_UNITS = {
    "": 1, "B": 1,
    "KB": 10**3, "MB": 10**6, "GB": 10**9, "TB": 10**12,
    "KIB": 2**10, "MIB": 2**20, "GIB": 2**30, "TIB": 2**40,
    # Bare K/M/G/T read as the binary units HBM sizes are quoted in.
    "K": 2**10, "M": 2**20, "G": 2**30, "T": 2**40,
}


def parse_size(s: str | int | float) -> int:
    """'16GiB' / '95 GB' / '1.5e9' / 8589934592 → bytes.

    Binary suffixes (KiB/MiB/GiB/TiB, or bare K/M/G/T) are powers of
    1024; decimal ones (KB/MB/GB/TB) powers of 1000.
    """
    if isinstance(s, (int, float)):
        return int(s)
    text = str(s).strip()
    i = len(text)
    while i > 0 and not (text[i - 1].isdigit() or text[i - 1] == "."):
        i -= 1
    num, unit = text[:i].strip(), text[i:].strip().upper()
    if not num or unit not in _SIZE_UNITS:
        raise ValueError(
            f"cannot parse size {s!r} — expected e.g. '16GiB', '32GB', "
            "or a plain byte count")
    return int(float(num) * _SIZE_UNITS[unit])


def detect(devices: Sequence[jax.Device] | None = None) -> Topology:
    """Discover the visible device topology.

    Equivalent of the reference's CUDA device enumeration, but also derives
    slice structure (for DCN-aware hybrid meshes) from device attributes.
    """
    devices = list(devices if devices is not None else jax.devices())
    slice_ids = {getattr(d, "slice_index", 0) or 0 for d in devices}
    num_slices = max(len(slice_ids), 1)
    return Topology(
        num_devices=len(devices),
        num_hosts=max(len({d.process_index for d in devices}), 1),
        platform=devices[0].platform if devices else "cpu",
        device_kind=devices[0].device_kind if devices else "unknown",
        num_slices=num_slices,
        devices_per_slice=len(devices) // num_slices if devices else None,
    )


def device_record() -> dict:
    """The device as JAX reports it, for a benchmark or smoke record:
    a number without this beside it does not say what it measured."""
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": jax.device_count()}


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    """Resolved per-axis parallelism degrees for a mesh build."""

    axes: Mapping[str, int]

    def degree(self, axis: str) -> int:
        return int(self.axes.get(axis, 1))

    @property
    def total(self) -> int:
        return math.prod(self.axes.values()) if self.axes else 1


def _resolve_degrees(
    num_devices: int, requested: Mapping[str, int | None]
) -> dict[str, int]:
    """Fill in unspecified (-1/None) axis degrees so the product covers all
    devices.  At most one axis may be -1; unmentioned axes get 1; if nothing
    is specified, everything goes to ``data``."""
    degrees: dict[str, int] = {}
    infer_axis: str | None = None
    for ax in MESH_AXES:
        v = requested.get(ax)
        if v in (-1, None) and ax in requested:
            if infer_axis is not None:
                raise ValueError(
                    f"At most one mesh axis may be -1 (got {infer_axis!r} and {ax!r})"
                )
            infer_axis = ax
        elif v is not None:
            if v < 1:
                raise ValueError(f"Axis {ax!r} degree must be >=1 or -1, got {v}")
            degrees[ax] = int(v)
    specified = math.prod(degrees.values()) if degrees else 1
    if infer_axis is not None:
        if num_devices % specified:
            raise ValueError(
                f"{num_devices} devices not divisible by specified axes product "
                f"{specified} ({degrees})"
            )
        degrees[infer_axis] = num_devices // specified
    elif not degrees:
        degrees["data"] = num_devices
    else:
        if specified != num_devices:
            # Auto-expand the data axis to absorb remaining devices.
            if num_devices % specified:
                raise ValueError(
                    f"Mesh axes {degrees} (product {specified}) do not divide "
                    f"{num_devices} devices"
                )
            degrees["data"] = degrees.get("data", 1) * (num_devices // specified)
    full = {ax: degrees.get(ax, 1) for ax in MESH_AXES}
    assert math.prod(full.values()) == num_devices
    return full


def hybrid_factorization(
    degrees: Mapping[str, int], num_slices: int
) -> tuple[list[int], list[int]] | None:
    """Split every mesh-axis degree into (in-slice, cross-slice) factors.

    Greedy gcd over the DCN-tolerant axes in MESH_AXES order: ``pipe``
    absorbs as much of the slice count as divides it, then ``data`` takes
    the rest — so BOTH may span DCN at once (e.g. 4 slices with pipe=2,
    data=2x in-slice batch).  ICI-critical axes (tensor/seq/expert/fsdp)
    never cross slices.  Returns ``(ici_shape, dcn_shape)`` ordered like
    MESH_AXES, or None when the DCN-tolerant degrees cannot cover the
    slice count (caller falls back to a flat mesh, loudly).
    """
    dcn_shape: list[int] = []
    ici_shape: list[int] = []
    remaining = num_slices
    for ax in MESH_AXES:
        d = int(degrees.get(ax, 1))
        if ax in DCN_OK_AXES and remaining > 1:
            g = math.gcd(d, remaining)
            dcn_shape.append(g)
            ici_shape.append(d // g)
            remaining //= g
        else:
            dcn_shape.append(1)
            ici_shape.append(d)
    if remaining != 1:
        return None
    return ici_shape, dcn_shape


def build_mesh(
    *,
    data: int | None = None,
    fsdp: int | None = None,
    tensor: int | None = None,
    seq: int | None = None,
    pipe: int | None = None,
    expert: int | None = None,
    devices: Sequence[jax.Device] | None = None,
    allow_split_physical_axes: bool = False,
) -> Mesh:
    """Build an ICI-aware ``jax.sharding.Mesh`` over the visible devices.

    Unspecified axes default to 1; pass ``-1`` for exactly one axis to infer
    its degree from the device count; with no axes specified all devices go
    to ``data`` (pure DP — the reference's DDP default, BASELINE.json:8).

    On real TPU slices ``mesh_utils.create_device_mesh`` orders devices so
    each mesh axis maps onto ICI rings; on multi-slice topologies a hybrid
    ICI x DCN mesh is built with DCN-tolerant axes (``pipe``, ``data``)
    spanning slices.
    """
    devices = list(devices if devices is not None else jax.devices())
    topo = detect(devices)
    requested = {
        "data": data,
        "fsdp": fsdp,
        "tensor": tensor,
        "seq": seq,
        "pipe": pipe,
        "expert": expert,
    }
    requested = {k: v for k, v in requested.items() if v is not None}
    degrees = _resolve_degrees(len(devices), requested)
    shape = tuple(degrees[ax] for ax in MESH_AXES)

    if topo.is_multislice and topo.devices_per_slice:
        fact = hybrid_factorization(degrees, topo.num_slices)
        if fact is not None:
            ici_shape, dcn_shape = fact
            assert math.prod(ici_shape) == topo.devices_per_slice
            dev_array = mesh_utils.create_hybrid_device_mesh(
                ici_shape,
                dcn_shape,
                devices=devices,
                allow_split_physical_axes=allow_split_physical_axes,
            )
            return Mesh(dev_array, MESH_AXES)
        # Loud fall-through: a flat mesh on a multi-slice topology puts
        # ICI-critical collectives on DCN — legal but slow, and the user
        # should know why and how to fix the axis degrees.
        warnings.warn(
            f"Cannot factor mesh axes {dict(degrees)} so that the "
            f"DCN-tolerant axes {DCN_OK_AXES} cover {topo.num_slices} "
            f"slices (their combined degree must be divisible by the "
            f"slice count). Falling back to a FLAT device mesh: "
            f"tensor/fsdp/expert collectives may cross DCN and be "
            f"slow. Raise the pipe/data degrees to a multiple of the "
            f"slice count to get a hybrid ICIxDCN mesh.",
            stacklevel=2,
        )

    try:
        dev_array = mesh_utils.create_device_mesh(
            shape, devices=devices,
            allow_split_physical_axes=allow_split_physical_axes,
        )
    except (ValueError, NotImplementedError, AssertionError):
        # CPU sim / odd topologies: plain row-major reshape is always valid.
        dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, MESH_AXES)


def single_device_mesh(device: jax.Device | None = None) -> Mesh:
    """Trivial 1-device mesh — the no-op path (BASELINE.json:7)."""
    device = device or jax.devices()[0]
    return Mesh(
        np.asarray([device]).reshape((1,) * len(MESH_AXES)), MESH_AXES
    )


def mesh_degrees(mesh: Mesh | Mapping[str, int]) -> dict[str, int]:
    """Axis-name -> degree of a ``Mesh``, or of a plain degrees mapping.

    Accepting a mapping lets the planner's pure functions
    (``param_spec_tree``, ``batch_partition_spec``,
    ``expected_collective_bytes``) run on *hypothetical* meshes — the
    tune/ subsystem scores candidate factorizations without ever
    building a device array.
    """
    if isinstance(mesh, Mapping):
        return {ax: int(n) for ax, n in mesh.items()}
    return {ax: int(n) for ax, n in zip(mesh.axis_names, mesh.devices.shape)}


# The persistent compile cache's home when JAX_COMPILATION_CACHE_DIR does
# not place it: one fixed path inside the checkout.  The path is part of
# the cache key, so it never carries a pid, a time or a temporary name.
_DEFAULT_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def compilation_cache_dir() -> tuple[str, str]:
    """``(path, source)`` of the persistent compile cache: ``"env"`` when
    ``JAX_COMPILATION_CACHE_DIR`` places it, else ``"default"`` (the
    fixed in-checkout path).  Touches nothing."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir, "env"
    return _DEFAULT_COMPILE_CACHE, "default"


def enable_compilation_cache() -> str | None:
    """Turn on JAX's persistent compilation cache; returns its directory
    (None when ``TADNN_NO_COMPILE_CACHE`` opts out).

    Big-model XLA:TPU compiles run tens of seconds; the cache amortizes
    them across process restarts — which the elastic story
    (training/elastic.py restart-based recovery) hits every resume.
    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and no
    directory is set in code; otherwise the cache goes to the fixed
    in-checkout path, created here.  Called by the entry points
    (``chip_smoke.py``, ``tadnn run|serve|fit``, ``benchmark/run.py``),
    never at import.  Safe to call more than once.
    """
    if os.environ.get("TADNN_NO_COMPILE_CACHE"):
        return None
    cache_dir, source = compilation_cache_dir()
    if source == "default":
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # cache everything that took meaningful compile time
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return cache_dir


def initialize_distributed(**kwargs) -> None:
    """Multi-host runtime init — the ``torchrun``/``mp.spawn`` analog (C9).

    Single-controller JAX needs no per-device spawn; on multi-host
    deployments each host calls this once (coordinator discovered from
    env or explicit kwargs).  No-op when single-process.
    """
    coord = kwargs.get("coordinator_address") or os.environ.get(
        "JAX_COORDINATOR_ADDRESS"
    )
    if not coord and "num_processes" not in kwargs:
        return  # single-process launch — nothing to initialize
    try:
        jax.distributed.initialize(**kwargs)
    except RuntimeError as e:
        if "already" in str(e).lower():
            return  # idempotent: a second call is a no-op
        raise
