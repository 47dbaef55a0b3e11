"""Communication backend (component C8).

Reference capability (SURVEY.md C8): NCCL allreduce / allgather /
reduce-scatter / broadcast via ``torch.distributed`` ProcessGroup.

TPU-native realization: XLA collectives over ICI (in-slice) and DCN
(cross-slice).  Under ``pjit``/GSPMD the compiler inserts them from the
sharding annotations; this module provides the *explicit* tier — thin,
named wrappers usable inside ``shard_map`` regions (ring attention,
pipeline ppermute, MoE all_to_all) — plus the allreduce bus-bandwidth
microbenchmark that BASELINE.json:2 names as a headline metric.

Bus bandwidth follows the NCCL-tests convention so numbers are comparable
with the reference's NCCL benchmarks: for allreduce on n devices,
``bus_bw = (2*(n-1)/n) * bytes / time``.
"""

from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map
from jax.lax import axis_size


# ---------------------------------------------------------------------------
# Explicit collectives (shard_map tier)
# ---------------------------------------------------------------------------

def allreduce(x: jax.Array, axis: str | tuple[str, ...]) -> jax.Array:
    """Sum-allreduce over a mesh axis (NCCL allreduce analog)."""
    return jax.lax.psum(x, axis)


def allmean(x: jax.Array, axis: str | tuple[str, ...]) -> jax.Array:
    return jax.lax.pmean(x, axis)


def allgather(x: jax.Array, axis: str, *, tiled: bool = True, gather_dim: int = 0) -> jax.Array:
    """Concatenate shards along ``gather_dim`` (NCCL allgather analog)."""
    return jax.lax.all_gather(x, axis, axis=gather_dim, tiled=tiled)


def reduce_scatter(x: jax.Array, axis: str, *, scatter_dim: int = 0) -> jax.Array:
    """Sum-reduce then scatter along ``scatter_dim`` (NCCL reduce-scatter)."""
    return jax.lax.psum_scatter(x, axis, scatter_dimension=scatter_dim, tiled=True)


def broadcast(x: jax.Array, axis: str, root: int = 0) -> jax.Array:
    """Every shard receives the root shard's value (NCCL broadcast analog).

    Implemented as all_gather + root slice: (n-1)/n bytes per rank on the
    wire — half the cost of the masked-psum formulation (an allreduce at
    2(n-1)/n), and the gather of non-root shards is dead weight the ring
    schedule absorbs.  Suitable for weight-sized payloads, not just
    scalars; transient memory is n * shard bytes.
    """
    g = jax.lax.all_gather(x, axis)  # [n, ...]
    return g[root]


def all_to_all(
    x: jax.Array, axis: str, *, split_dim: int, concat_dim: int
) -> jax.Array:
    """Transpose shard ownership (Ulysses / MoE dispatch primitive)."""
    return jax.lax.all_to_all(
        x, axis, split_axis=split_dim, concat_axis=concat_dim, tiled=True
    )


def ppermute_ring(x: jax.Array, axis: str, shift: int = 1) -> jax.Array:
    """Rotate shards around the ring (ring attention / pipeline hop)."""
    n = axis_size(axis)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return jax.lax.ppermute(x, axis, perm)


def axis_index(axis: str) -> jax.Array:
    return jax.lax.axis_index(axis)


# ---------------------------------------------------------------------------
# Microbenchmark (BASELINE.json:2 — allreduce bus bandwidth)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class CollectiveBenchResult:
    op: str
    n_devices: int
    size_bytes: int
    time_s: float
    alg_bw_gbps: float  # bytes / time
    bus_bw_gbps: float  # NCCL-tests bus-bandwidth convention

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


def _bus_factor(op: str, n: int) -> float:
    if op == "allreduce":
        return 2 * (n - 1) / n
    if op in ("allgather", "reduce_scatter"):
        return (n - 1) / n
    if op == "all_to_all":
        return (n - 1) / n
    return 1.0


def bench_collective(
    op: str = "allreduce",
    size_bytes: int = 64 * 2**20,
    *,
    mesh: Mesh | None = None,
    axis: str = "data",
    iters: int = 10,
    warmup: int = 3,
    dtype=jnp.float32,
) -> CollectiveBenchResult:
    """Time one collective over one mesh axis; report alg + bus bandwidth."""
    if mesh is None:
        from .. import topology

        mesh = topology.build_mesh(data=-1)
    n = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    itemsize = jnp.dtype(dtype).itemsize
    per_dev = max(size_bytes // itemsize, n)
    per_dev -= per_dev % n  # divisible for scatter ops
    ops: dict[str, Callable] = {
        "allreduce": lambda x: jax.lax.psum(x, axis),
        "allgather": lambda x: jax.lax.all_gather(x, axis, tiled=True),
        "reduce_scatter": lambda x: jax.lax.psum_scatter(x, axis, tiled=True),
        "all_to_all": lambda x: jax.lax.all_to_all(
            x, axis, split_axis=0, concat_axis=0, tiled=True
        ),
        "ppermute": lambda x: ppermute_ring(x, axis),
    }
    fn = ops[op]
    out_specs = {
        "allreduce": P(axis),   # per-shard result, same shape as shard
        "allgather": P(axis),   # every shard holds the full gather
        "reduce_scatter": P(axis),
        "all_to_all": P(axis),
        "ppermute": P(axis),
    }[op]

    @partial(
        shard_map, mesh=mesh, in_specs=P(axis), out_specs=out_specs,
        check_vma=False,
    )
    def run(x):
        return fn(x)

    x = jnp.ones((per_dev * n,), dtype)
    x = jax.device_put(x, NamedSharding(mesh, P(axis)))

    # Each sample chains `iters` collectives inside one jit (inputs
    # perturbed per iteration to defeat CSE), fenced once.
    @jax.jit
    def run_n(x):
        def body(i, acc):
            # O(1) perturbation: serializing data dependency on acc
            # without a full-buffer elementwise pass or dtype promotion
            xx = x.at[0].add((acc * 0).astype(x.dtype))
            y = run(xx)
            return acc + y.reshape(-1)[0].astype(jnp.float32)
        return jax.lax.fori_loop(0, iters, body, jnp.float32(0))

    for _ in range(warmup):
        jax.block_until_ready(run_n(x))
    t0 = time.perf_counter()
    total = float(jax.block_until_ready(run_n(x)))
    assert total == total
    t = max(time.perf_counter() - t0, 1e-9) / iters
    # NCCL-tests convention: bandwidth is computed from the PER-RANK buffer
    # size, not the global array size.
    nbytes = per_dev * itemsize
    alg = nbytes / t / 1e9
    return CollectiveBenchResult(
        op=op,
        n_devices=n,
        size_bytes=nbytes,
        time_s=t,
        alg_bw_gbps=alg,
        bus_bw_gbps=alg * _bus_factor(op, n),
    )


def bench_sweep(
    sizes: Sequence[int] = (2**20, 2**24, 2**27),
    ops: Sequence[str] = ("allreduce", "allgather", "reduce_scatter"),
    **kwargs,
) -> list[CollectiveBenchResult]:
    return [bench_collective(op, s, **kwargs) for op in ops for s in sizes]


# ---------------------------------------------------------------------------
# Comm/compute overlap microbenchmark (component C4)
# ---------------------------------------------------------------------------
#
# The reference's bucketed DDP overlaps gradient allreduce with the rest of
# the backward pass (BASELINE.json:9).  The TPU-native analog delegates that
# scheduling to XLA's latency-hiding scheduler — this benchmark MEASURES
# whether the overlap actually happens instead of asserting it: a chain of
# L matmul "layers" each releasing a psum "bucket" that only depends on its
# own layer (the DDP dependency shape), timed against compute-only and
# comm-only baselines.
#
#   overlap_frac = (t_compute + t_comm - t_both) / min(t_compute, t_comm)
#
# 1.0 = the cheaper phase fully hidden; 0.0 = fully serialized.
#
# Recommended TPU flags (set in XLA_FLAGS before process start; they steer
# the scheduler, they do not change semantics):
#   --xla_tpu_enable_latency_hiding_scheduler=true

LATENCY_HIDING_XLA_FLAGS = "--xla_tpu_enable_latency_hiding_scheduler=true"


@dataclasses.dataclass
class OverlapBenchResult:
    n_devices: int
    layers: int
    t_compute_s: float
    t_comm_s: float
    t_both_s: float
    overlap_frac: float
    bucket_bytes: int

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


def bench_overlap(
    *,
    mesh: Mesh | None = None,
    axis: str = "data",
    d: int = 512,
    layers: int = 8,
    bucket_bytes: int = 2**22,
    iters: int = 5,
    warmup: int = 2,
) -> OverlapBenchResult:
    """Measure how much gradient-bucket psum the scheduler hides behind
    the matmul chain (the bucketed-DDP shape, component C4)."""
    if mesh is None:
        from .. import topology

        mesh = topology.build_mesh(data=-1)
    n = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    m = max(bucket_bytes // 4, 128)
    key = jax.random.key(0)
    w = jax.random.normal(key, (d, d), jnp.float32) / np.sqrt(d)
    x0 = jax.random.normal(key, (d, d), jnp.float32)
    buf = jnp.ones((m,), jnp.float32)

    def layer(y):
        return jax.lax.dot(y, w, precision=jax.lax.Precision.DEFAULT)

    def compute_only(y, _buf):
        acc = jnp.float32(0)
        for _ in range(layers):
            y = layer(y)
            acc = acc + y[0, 0]
        return acc

    def comm_only(y, b):
        acc = jnp.float32(0)
        for i in range(layers):
            # per-bucket payload differs (defeats CSE); no matmul feeds it
            g = jax.lax.psum(b + jnp.float32(i), axis)
            acc = acc + g[0]
        return acc

    def both(y, b):
        acc = jnp.float32(0)
        for _ in range(layers):
            y = layer(y)
            # DDP shape: bucket i depends on layer i only — the scheduler
            # may overlap its psum with layer i+1's matmul
            g = jax.lax.psum(b + y[0, 0], axis)
            acc = acc + g[0]
        return acc

    def timed(fn):
        smapped = shard_map(
            fn, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
            check_vma=False,
        )

        @jax.jit
        def run_n(x, b):
            def body(i, carry):
                out = smapped(x + (carry * 0), b)
                return carry + out
            return jax.lax.fori_loop(0, iters, body, jnp.float32(0))

        for _ in range(warmup):
            jax.block_until_ready(run_n(x0, buf))
        t0 = time.perf_counter()
        total = float(jax.block_until_ready(run_n(x0, buf)))
        assert total == total
        return max(time.perf_counter() - t0, 1e-9) / iters

    tc = timed(compute_only)
    tm = timed(comm_only)
    tb = timed(both)
    frac = (tc + tm - tb) / max(min(tc, tm), 1e-9)
    return OverlapBenchResult(
        n_devices=n,
        layers=layers,
        t_compute_s=tc,
        t_comm_s=tm,
        t_both_s=tb,
        overlap_frac=max(min(frac, 1.0), -1.0),
        bucket_bytes=m * 4,
    )
