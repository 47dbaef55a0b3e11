

def test_overlap_bench_runs(devices8):
    """C4 overlap microbench: fields are consistent; no overlap claim is
    made on the CPU sim (shared host cores), only that the measurement
    machinery works."""
    from torch_automatic_distributed_neural_network_tpu.parallel.collectives import (
        bench_overlap,
    )

    r = bench_overlap(d=128, layers=3, bucket_bytes=2**16, iters=2, warmup=1)
    assert r.n_devices == 8
    assert r.t_compute_s > 0 and r.t_comm_s > 0 and r.t_both_s > 0
    assert -1.0 <= r.overlap_frac <= 1.0

def test_broadcast_delivers_root_shard(devices8):
    """broadcast: every shard receives the root shard's value (all_gather
    + root-slice formulation, half the wire cost of a masked psum)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from functools import partial
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    import torch_automatic_distributed_neural_network_tpu as tad
    from torch_automatic_distributed_neural_network_tpu.parallel.collectives import (
        broadcast,
    )

    mesh = tad.build_mesh(data=8)
    x = jnp.arange(8 * 4, dtype=jnp.float32).reshape(8, 4)

    @partial(shard_map, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
             check_vma=False)
    def run(shard):
        return broadcast(shard, "data", root=3)

    out = np.asarray(run(x))
    # every device's output row equals root device 3's input row
    for i in range(8):
        np.testing.assert_array_equal(out[i], np.asarray(x)[3])
