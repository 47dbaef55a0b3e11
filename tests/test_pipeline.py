"""Pipeline parallelism tests (SURVEY.md §2.2 'PP', §4 CPU-sim tier).

Oracle pattern (SURVEY.md §3.5): the sequential single-program run is the
ground truth; the pipelined program must match it numerically — forward,
gradients, and the full AutoDistribute loss trajectory.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

import torch_automatic_distributed_neural_network_tpu as tad
from torch_automatic_distributed_neural_network_tpu.models import (
    DecoderLM,
    TransformerConfig,
)
from torch_automatic_distributed_neural_network_tpu.parallel import pipeline
from torch_automatic_distributed_neural_network_tpu.training import (

    next_token_loss,
)

TINY = TransformerConfig(
    vocab_size=512,
    d_model=64,
    n_layers=4,
    n_heads=4,
    max_seq_len=32,
    dtype=jnp.float32,  # exact parity checks
)


# Minutes-scale on the 8-device CPU sim (every case is a fresh
# multi-device XLA compile): excluded from the quick tier-1 pass,
# run with -m slow (or no marker filter) for full coverage.
pytestmark = pytest.mark.slow

def _mesh(devs, shape, names):
    return Mesh(np.array(devs).reshape(shape), names)


class TestSpmdPipeline:
    def test_forward_and_grad_parity(self, devices8):
        mesh = _mesh(devices8[:4], (4,), ("pipe",))
        L, D, M, MB = 8, 16, 4, 2
        W = jax.random.normal(jax.random.key(0), (L, D, D)) * 0.1
        x = jax.random.normal(jax.random.key(1), (M, MB, D))

        def stage_fn(w_stack, h, mb_idx):
            def body(c, w):
                return jnp.tanh(c @ w), None

            return jax.lax.scan(body, h, w_stack)[0]

        pipe = shard_map(
            lambda w, mbs: pipeline.spmd_pipeline(
                stage_fn, w, mbs, n_stages=4, axis_name="pipe"
            ),
            mesh=mesh,
            in_specs=(P("pipe"), P()),
            out_specs=P(),
        )

        ref = x
        for i in range(L):
            ref = jnp.tanh(ref @ W[i])
        out = jax.jit(pipe)(W, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)

        g_pipe = jax.jit(jax.grad(lambda w: jnp.sum(pipe(w, x) ** 2)))(W)

        def seq_loss(w):
            h = x
            for i in range(L):
                h = jnp.tanh(h @ w[i])
            return jnp.sum(h**2)

        g_ref = jax.jit(jax.grad(seq_loss))(W)
        np.testing.assert_allclose(
            np.asarray(g_pipe), np.asarray(g_ref), atol=1e-5
        )

    def test_with_data_axis(self, devices8):
        """pipe x data mesh: batch sharded over data, pipeline over pipe."""
        mesh = _mesh(devices8, (2, 4), ("pipe", "data"))
        L, D, M, B = 4, 8, 2, 8
        W = jax.random.normal(jax.random.key(0), (L, D, D)) * 0.1
        x = jax.random.normal(jax.random.key(1), (M, B, D))

        def stage_fn(w_stack, h, mb_idx):
            return jax.lax.scan(
                lambda c, w: (jnp.tanh(c @ w), None), h, w_stack
            )[0]

        pipe = shard_map(
            lambda w, mbs: pipeline.spmd_pipeline(
                stage_fn, w, mbs, n_stages=2, axis_name="pipe"
            ),
            mesh=mesh,
            in_specs=(P("pipe"), P(None, "data")),
            out_specs=P(None, "data"),
        )
        ref = x
        for i in range(L):
            ref = jnp.tanh(ref @ W[i])
        out = jax.jit(pipe)(W, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)

    def test_stage_shape_mismatch_raises(self, devices8):
        mesh = _mesh(devices8[:2], (2,), ("pipe",))
        W = jnp.zeros((2, 4, 8))
        x = jnp.zeros((2, 2, 4))

        def bad_stage(w, h, mb_idx):  # changes the trailing dim
            return h @ w[0]

        pipe = shard_map(
            lambda w, mbs: pipeline.spmd_pipeline(
                bad_stage, w, mbs, n_stages=2, axis_name="pipe"
            ),
            mesh=mesh,
            in_specs=(P("pipe"), P()),
            out_specs=P(),
        )
        with pytest.raises(ValueError, match="preserve activation"):
            jax.jit(pipe)(W, x)

    def test_bubble_fraction(self):
        assert pipeline.bubble_fraction(1, 8) == 0.0
        assert pipeline.bubble_fraction(4, 4) == pytest.approx(3 / 7)


class TestPipelinedApply:
    def test_logits_parity_with_model(self, devices8):
        """Pipelined apply == plain model.apply (drift guard for the
        mirrored embed/head glue in make_pipelined_apply)."""
        mesh = _mesh(devices8[:2], (2,), ("pipe",))
        model = DecoderLM(TINY)
        tokens = jax.random.randint(jax.random.key(0), (4, 16), 0, 512)
        variables = model.init(jax.random.key(1), tokens)
        ref = model.apply(variables, tokens)
        papply = pipeline.make_pipelined_apply(model, mesh, n_microbatches=2)
        out = jax.jit(papply)(variables, tokens)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
        )

    def test_rmsnorm_rope_untied_variant(self, devices8):
        mesh = _mesh(devices8[:4], (4,), ("pipe",))
        cfg = TransformerConfig(
            vocab_size=256, d_model=64, n_layers=4, n_heads=4,
            n_kv_heads=2, max_seq_len=32, norm="rmsnorm", act="swiglu",
            pos="rope", tie_embeddings=False, dtype=jnp.float32,
        )
        model = DecoderLM(cfg)
        tokens = jax.random.randint(jax.random.key(0), (4, 16), 0, 256)
        variables = model.init(jax.random.key(1), tokens)
        ref = model.apply(variables, tokens)
        papply = pipeline.make_pipelined_apply(model, mesh, n_microbatches=4)
        out = jax.jit(papply)(variables, tokens)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
        )

    def test_custom_positions_and_mask_thread_through_stages(self, devices8):
        """Round-2 gap: pipelined apply raised NotImplementedError on
        custom positions/mask.  Now they replicate into the region and
        each stage indexes its microbatch's slice — parity with plain
        model.apply on a rope model with a padding mask."""
        mesh = _mesh(devices8[:2], (2,), ("pipe",))
        cfg = TransformerConfig(
            vocab_size=256, d_model=64, n_layers=4, n_heads=4,
            max_seq_len=64, norm="rmsnorm", act="swiglu", pos="rope",
            dtype=jnp.float32,
        )
        model = DecoderLM(cfg)
        B, S = 4, 16
        tokens = jax.random.randint(jax.random.key(0), (B, S), 0, 256)
        # shifted positions (as in packed/continued sequences) + padding
        # mask hiding the last 3 keys of every row
        positions = jnp.broadcast_to(jnp.arange(S)[None, :] + 5, (B, S))
        mask = jnp.broadcast_to(
            (jnp.arange(S) < S - 3)[None, None, None, :], (B, 1, 1, S)
        )
        variables = model.init(jax.random.key(1), tokens)
        ref = model.apply(variables, tokens, positions, mask)
        papply = pipeline.make_pipelined_apply(model, mesh, n_microbatches=2)
        out = jax.jit(papply)(variables, tokens, positions, mask)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
        )
        # broadcastable extras (leading dim 1) work like plain apply
        out_b = jax.jit(papply)(
            variables, tokens, positions[:1], mask[:1]
        )
        np.testing.assert_allclose(
            np.asarray(out_b), np.asarray(ref), atol=2e-5, rtol=2e-5
        )
        # and the default path (no extras) still matches
        ref0 = model.apply(variables, tokens)
        out0 = jax.jit(papply)(variables, tokens)
        np.testing.assert_allclose(
            np.asarray(out0), np.asarray(ref0), atol=2e-5, rtol=2e-5
        )

    def test_rejects_indivisible_layers(self, devices8):
        mesh = _mesh(devices8[:4], (4,), ("pipe",))
        cfg = TransformerConfig(
            vocab_size=64, d_model=32, n_layers=6, n_heads=2, max_seq_len=16
        )
        with pytest.raises(ValueError, match="not divisible"):
            pipeline.make_pipelined_apply(DecoderLM(cfg), mesh)


class TestAutoDistributePipeline:
    def test_loss_trajectory_matches_dp(self, devices8):
        """pipe=2 x data=4 matches pure-DP — the §3.5 oracle."""
        tokens = np.asarray(
            jax.random.randint(jax.random.key(9), (8, 17), 0, 512)
        )
        batch = {"input_ids": tokens}

        def make(**kw):
            ad = tad.AutoDistribute(
                DecoderLM(TINY),
                optimizer=optax.sgd(0.1),
                loss_fn=next_token_loss,
                **kw,
            )
            state = ad.init(jax.random.key(0), batch)
            losses = []
            for _ in range(4):
                state, m = ad.step(state, batch)
                losses.append(float(m["loss"]))
            return losses

        ref = make(strategy="dp")
        got = make(strategy="dp", pipeline_stages=2, microbatches=2)
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)

    def test_cond_and_dense_schedules_match(self, devices8):
        """'cond' (bubbles skip compute via lax.cond) and 'dense' (round-2
        compute-and-mask) must be trajectory-identical: cond only removes
        work whose results were discarded anyway."""
        tokens = np.asarray(
            jax.random.randint(jax.random.key(11), (8, 17), 0, 512)
        )
        batch = {"input_ids": tokens}

        def run(sched):
            ad = tad.AutoDistribute(
                DecoderLM(TINY),
                optimizer=optax.sgd(0.1),
                loss_fn=next_token_loss,
                strategy="dp",
                pipeline_stages=4,
                microbatches=2,  # S-1 > M: bubbles dominate — worst case
                pipeline_schedule=sched,
            )
            state = ad.init(jax.random.key(0), batch)
            losses = []
            for _ in range(3):
                state, m = ad.step(state, batch)
                losses.append(float(m["loss"]))
            return losses

        np.testing.assert_allclose(run("cond"), run("dense"), rtol=1e-6)

    def test_1f1b_matches_cond(self, devices8):
        """'1f1b' (hand-scheduled custom_vjp backward with the 2S-1 stash
        ring) must be trajectory-identical to 'cond' (AD through the
        GPipe scan) — same math, different schedule and memory bound."""
        tokens = np.asarray(
            jax.random.randint(jax.random.key(12), (16, 17), 0, 512)
        )
        batch = {"input_ids": tokens}

        def run(sched, stages, mbs):
            ad = tad.AutoDistribute(
                DecoderLM(TINY),
                optimizer=optax.sgd(0.1),
                loss_fn=next_token_loss,
                strategy="dp",
                pipeline_stages=stages,
                microbatches=mbs,
                pipeline_schedule=sched,
            )
            state = ad.init(jax.random.key(0), batch)
            losses = []
            for _ in range(3):
                state, m = ad.step(state, batch)
                losses.append(float(m["loss"]))
            return losses

        # per-device batch (8 / data_degree) must divide microbatches.
        # M > S configs are the schedule's target regime AND the one
        # where the stash-ring read/write ordering matters (a
        # read-after-write regression corrupts stage-0 gradients
        # exactly when M > S — caught by (2, 4) and (4, 4) here).
        for stages, mbs in ((2, 2), (2, 4), (4, 4)):
            np.testing.assert_allclose(
                run("1f1b", stages, mbs), run("cond", stages, mbs),
                rtol=1e-6,
            )

    def test_1f1b_pipe_x_tensor(self, devices8):
        """1f1b composes with tensor parallelism inside the stages the
        same way cond does (the explicit vjp differentiates the stage's
        GSPMD-auto matmuls)."""
        tokens = np.asarray(
            jax.random.randint(jax.random.key(13), (8, 17), 0, 512)
        )
        batch = {"input_ids": tokens}

        def run(**kw):
            ad = tad.AutoDistribute(
                DecoderLM(TINY),
                optimizer=optax.sgd(0.1),
                loss_fn=next_token_loss,
                **kw,
            )
            state = ad.init(jax.random.key(0), batch)
            losses = []
            for _ in range(3):
                state, m = ad.step(state, batch)
                losses.append(float(m["loss"]))
            return losses, ad

        ref, _ = run(strategy="dp")
        got, ad = run(strategy="tp", pipeline_stages=2, microbatches=2,
                      pipeline_schedule="1f1b")
        d = tad.mesh_degrees(ad.plan.mesh)
        assert d["pipe"] == 2 and d["tensor"] == 4
        np.testing.assert_allclose(got, ref, rtol=2e-4)

    def test_1f1b_dropout_uses_cond_and_matches_dense(self, devices8):
        """With dropout on, 'cond'/'dense' fall back to dense under AD,
        but 1f1b's forward is never differentiated, so it keeps the
        bubble skip — and the per-(microbatch, layer) rng folding is
        schedule-independent, so the trajectory still matches 'dense'
        exactly."""
        cfg = TransformerConfig(
            vocab_size=256, d_model=64, n_layers=4, n_heads=4,
            max_seq_len=32, dropout_rate=0.25, dtype=jnp.float32,
        )
        tokens = np.asarray(
            jax.random.randint(jax.random.key(14), (8, 17), 0, 256)
        )
        batch = {"input_ids": tokens}

        def run(sched):
            ad = tad.AutoDistribute(
                DecoderLM(cfg),
                optimizer=optax.sgd(0.1),
                loss_fn=next_token_loss,
                strategy="dp",
                pipeline_stages=2,
                microbatches=2,
                pipeline_schedule=sched,
            )
            state = ad.init(jax.random.key(0), batch)
            losses = []
            for _ in range(3):
                state, m = ad.step(state, batch)
                losses.append(float(m["loss"]))
            return losses

        np.testing.assert_allclose(run("1f1b"), run("dense"), rtol=1e-6)

    def test_1f1b_memory_bound(self, devices8):
        """The point of 1F1B: compiled temp memory at M=8 microbatches
        must be strictly below the AD-GPipe ('cond') schedule's, whose
        live activation set grows with M (M+S-1 stashes vs the 2S-1
        ring + custom_vjp residual)."""
        from torch_automatic_distributed_neural_network_tpu.utils.profiling import (
            compiled_memory,
        )

        tokens = np.asarray(
            jax.random.randint(jax.random.key(15), (32, 33), 0, 512)
        )
        batch = {"input_ids": tokens}

        def temp_bytes(sched):
            ad = tad.AutoDistribute(
                DecoderLM(TINY),
                optimizer=optax.sgd(0.1),
                loss_fn=next_token_loss,
                strategy="dp",
                pipeline_stages=2,
                microbatches=8,
                pipeline_schedule=sched,
            )
            state = ad.init(jax.random.key(0), batch)
            mem = compiled_memory(ad._step_fn, state, ad.shard_batch(batch))
            assert mem is not None
            return mem["temp_size"]

        t_1f1b, t_cond = temp_bytes("1f1b"), temp_bytes("cond")
        assert t_1f1b < t_cond, (t_1f1b, t_cond)

    def test_pipe_x_fsdp_trajectory(self, devices8):
        """pipe=2 x fsdp=4 matches pure-DP: ZeRO-3 param sharding on the
        stacked layer weights' trailing dims partitions inside the
        partial-manual region's auto axes (README composition matrix)."""
        tokens = np.asarray(
            jax.random.randint(jax.random.key(9), (8, 17), 0, 512)
        )
        batch = {"input_ids": tokens}

        def make(**kw):
            ad = tad.AutoDistribute(
                DecoderLM(TINY),
                optimizer=optax.sgd(0.1),
                loss_fn=next_token_loss,
                **kw,
            )
            state = ad.init(jax.random.key(0), batch)
            losses = []
            for _ in range(3):
                state, m = ad.step(state, batch)
                losses.append(float(m["loss"]))
            return losses, ad

        ref, _ = make(strategy="dp")
        got, ad = make(strategy="fsdp", pipeline_stages=2, microbatches=2)
        d = tad.mesh_degrees(ad.plan.mesh)
        assert d["pipe"] == 2 and d["fsdp"] == 4
        np.testing.assert_allclose(got, ref, rtol=2e-4)

    def test_plan_shards_layer_stack_on_pipe(self, devices8):
        ad = tad.AutoDistribute(
            DecoderLM(TINY),
            optimizer=optax.sgd(0.1),
            loss_fn=next_token_loss,
            strategy="dp",
            pipeline_stages=4,
            microbatches=2,
        )
        batch = {"input_ids": np.zeros((8, 17), np.int32)}
        plan = ad.build_plan(jax.random.key(0), batch)
        assert plan.mesh.shape["pipe"] == 4
        flat = jax.tree_util.tree_flatten_with_path(
            plan.param_specs, is_leaf=lambda x: isinstance(x, P)
        )[0]
        layer_specs = [
            spec
            for path, spec in flat
            if "layers" in "/".join(str(getattr(k, "key", k)) for k in path)
        ]
        assert layer_specs and all(
            spec[0] == "pipe" for spec in layer_specs
        )


class TestPipelineV2:
    def test_pipe_x_tensor_trajectory(self, devices8):
        """pipe=2 x tensor=2 x data=2 matches pure-DP (stage-local TP via
        the partial-manual region's auto axes)."""
        tokens = np.asarray(
            jax.random.randint(jax.random.key(9), (8, 17), 0, 512)
        )
        batch = {"input_ids": tokens}

        def make(**kw):
            ad = tad.AutoDistribute(
                DecoderLM(TINY),
                optimizer=optax.sgd(0.1),
                loss_fn=next_token_loss,
                **kw,
            )
            state = ad.init(jax.random.key(0), batch)
            losses = []
            for _ in range(4):
                state, m = ad.step(state, batch)
                losses.append(float(m["loss"]))
            return losses, ad

        ref, _ = make(strategy="dp")
        got, ad = make(strategy="tp", pipeline_stages=2, microbatches=2)
        d = tad.mesh_degrees(ad.plan.mesh)
        assert d["pipe"] == 2 and d["tensor"] == 4
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)

    def test_pipe_x_tensor_param_specs(self, devices8):
        """Stacked layer weights carry pipe on the stack dim AND the
        Megatron col/row split on trailing dims."""
        ad = tad.AutoDistribute(
            DecoderLM(TINY),
            optimizer=optax.sgd(0.1),
            loss_fn=next_token_loss,
            strategy="tp",
            pipeline_stages=2,
            microbatches=2,
        )
        batch = {"input_ids": np.zeros((8, 17), np.int32)}
        plan = ad.build_plan(jax.random.key(0), batch)
        flat = jax.tree_util.tree_flatten_with_path(
            plan.param_specs, is_leaf=lambda x: isinstance(x, P)
        )[0]
        by_path = {
            "/".join(str(getattr(k, "key", k)) for k in path): spec
            for path, spec in flat
        }
        qproj = next(v for k, v in by_path.items() if "q_proj/kernel" in k)
        assert qproj[0] == "pipe", qproj
        assert "tensor" in qproj, qproj  # col-split survives under pipe

    def test_dropout_threads_through_stages(self, devices8):
        """Dropout in the pipelined trunk: deterministic per rng,
        different across rngs, and the loss path stays finite."""
        cfg = TransformerConfig(
            vocab_size=256, d_model=64, n_layers=4, n_heads=4,
            max_seq_len=32, dropout_rate=0.5, dtype=jnp.float32,
        )
        mesh = _mesh(devices8[:2], (2,), ("pipe",))
        model = DecoderLM(cfg)
        tokens = jax.random.randint(jax.random.key(0), (4, 16), 0, 256)
        variables = model.init(jax.random.key(1), tokens)
        papply = pipeline.make_pipelined_apply(model, mesh, n_microbatches=2)
        r1 = {"dropout": jax.random.key(7)}
        r2 = {"dropout": jax.random.key(8)}
        a = jax.jit(papply)(variables, tokens, rngs=r1)
        b = jax.jit(papply)(variables, tokens, rngs=r1)
        c = jax.jit(papply)(variables, tokens, rngs=r2)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert not np.allclose(np.asarray(a), np.asarray(c))
        assert np.isfinite(np.asarray(a)).all()

    def test_dropout_rng_optional_missing_means_off(self, devices8):
        """flax missing-rng convention (round-3: replaced the old
        ValueError): no dropout key -> deterministic pass, matching plain
        model.apply without rngs — what eval_step relies on; passing a
        key actually drops (output differs)."""
        cfg = TransformerConfig(
            vocab_size=64, d_model=32, n_layers=2, n_heads=2,
            max_seq_len=16, dropout_rate=0.5, dtype=jnp.float32,
        )
        mesh = _mesh(devices8[:2], (2,), ("pipe",))
        model = DecoderLM(cfg)
        tokens = jax.random.randint(jax.random.key(2), (2, 8), 0, 64)
        variables = model.init(jax.random.key(0), tokens)
        papply = pipeline.make_pipelined_apply(model, mesh, n_microbatches=2)
        det = jax.jit(papply)(variables, tokens)
        ref = model.apply(variables, tokens)  # no rngs -> dropout off
        np.testing.assert_allclose(
            np.asarray(det), np.asarray(ref), atol=2e-5, rtol=2e-5
        )
        dropped = jax.jit(papply)(
            variables, tokens, rngs={"dropout": jax.random.key(7)}
        )
        assert not np.allclose(np.asarray(dropped), np.asarray(det))

    def test_dropout_trains_under_default_cond_schedule(self, devices8):
        """Regression: the 'cond' schedule with dropout rngs trips a JAX
        cond-partial-eval internal assertion under AD (branch-asymmetric
        PRNG residuals) — the pipeline must auto-downgrade dropout models
        to 'dense'.  This trains (grad, not just forward) and evals."""
        import optax

        from torch_automatic_distributed_neural_network_tpu.data.synthetic import (
            SyntheticLM,
        )
        from torch_automatic_distributed_neural_network_tpu.training import (
            next_token_loss,
        )

        cfg = TransformerConfig(
            vocab_size=128, d_model=32, n_layers=2, n_heads=2,
            max_seq_len=16, dropout_rate=0.1, dtype=jnp.float32,
        )
        data = SyntheticLM(vocab_size=128, seq_len=17, batch_size=8)
        ad = tad.AutoDistribute(
            DecoderLM(cfg), optimizer=optax.sgd(0.1),
            loss_fn=next_token_loss, strategy="dp",
            pipeline_stages=2, microbatches=2,  # default schedule: cond
        )
        state = ad.init(jax.random.key(0), data.batch(0))
        state, m = ad.step(state, data.batch(0))
        assert np.isfinite(float(m["loss"]))
        e1 = ad.eval_step(state, data.batch(1))
        e2 = ad.eval_step(state, data.batch(1))
        assert float(e1["loss"]) == float(e2["loss"])  # dropout off in eval


class TestInterleaved:
    """Megatron interleaved schedule: V virtual stages per device over
    the [V, S, C] reshape view (parallel/pipeline.py r4)."""

    def _run(self, sched, stages, mbs, virtual=1, n_layers=8,
             dropout=0.0, seed=12):
        tokens = np.asarray(
            jax.random.randint(jax.random.key(seed), (16, 17), 0, 512)
        )
        batch = {"input_ids": tokens}
        cfg = dataclasses.replace(TINY, n_layers=n_layers,
                                  dropout_rate=dropout)
        ad = tad.AutoDistribute(
            DecoderLM(cfg),
            optimizer=optax.sgd(0.1),
            loss_fn=next_token_loss,
            strategy="dp",
            pipeline_stages=stages,
            microbatches=mbs,
            pipeline_schedule=sched,
            pipeline_virtual=virtual,
        )
        state = ad.init(jax.random.key(0), batch)
        losses = []
        for _ in range(3):
            state, m = ad.step(state, batch)
            losses.append(float(m["loss"]))
        return losses

    def test_matches_cond_trajectory(self, devices8):
        """V=2 and V=4 over 8 layers on 2 stages; V=2 on 4 stages —
        all must match the plain GPipe cond schedule exactly."""
        for stages, mbs, virtual in ((2, 2, 2), (2, 4, 4), (4, 4, 2)):
            np.testing.assert_allclose(
                self._run("interleaved", stages, mbs, virtual),
                self._run("cond", stages, mbs),
                rtol=1e-6,
            )

    def test_matches_oracle_1dev(self, devices8):
        tokens = np.asarray(
            jax.random.randint(jax.random.key(3), (16, 17), 0, 512)
        )
        batch = {"input_ids": tokens}
        cfg = dataclasses.replace(TINY, n_layers=8)

        def run(devs, **kw):
            ad = tad.AutoDistribute(
                DecoderLM(cfg), optimizer=optax.sgd(0.1),
                loss_fn=next_token_loss, strategy="dp", devices=devs, **kw,
            )
            state = ad.init(jax.random.key(0), batch)
            out = []
            for _ in range(3):
                state, m = ad.step(state, batch)
                out.append(float(m["loss"]))
            return out

        oracle = run(jax.devices()[:1])
        inter = run(jax.devices(), pipeline_stages=4, microbatches=4,
                    pipeline_schedule="interleaved", pipeline_virtual=2)
        np.testing.assert_allclose(inter, oracle, rtol=2e-4, atol=2e-4)

    def test_dropout_deterministic_and_schedule_independent(self, devices8):
        """With dropout on, interleaved (dense fallback under AD) must
        match the cond/dense schedules: rng streams are keyed by
        (microbatch, global layer), which the [V,S,C] view re-derives."""
        a = self._run("interleaved", 2, 4, 2, dropout=0.1)
        b = self._run("dense", 2, 4, dropout=0.1)
        np.testing.assert_allclose(a, b, rtol=1e-6)

    def test_validation_errors(self, devices8):
        with pytest.raises(ValueError, match="virtual >= 2"):
            self._run("interleaved", 2, 2, 1)
        with pytest.raises(ValueError, match="not divisible"):
            self._run("interleaved", 2, 2, 3, n_layers=8)  # 8 % 6 != 0
        with pytest.raises(ValueError, match="microbatches % stages"):
            self._run("interleaved", 4, 2, 2)  # M=2 < S=4
        with pytest.raises(ValueError, match="only applies"):
            self._run("cond", 2, 2, 2)  # virtual with non-interleaved

    def test_plain_1f1b_rejects_virtual(self, devices8):
        # virtual stages need the interleaved schedules; plain 1f1b
        # with virtual>1 is a config error, not a silent ignore
        with pytest.raises(ValueError, match="only applies"):
            self._run("1f1b", 2, 4, 2)

    def test_interleaved_1f1b_matches_cond(self, devices8):
        """The combined schedule: interleaved forward under custom_vjp
        + the hand-scheduled backward over the REVERSED chunk chain
        (onef_oneb_grads_interleaved).  Trajectory-identical to cond;
        memory bounded by the 2VS-1 stash ring instead of MV."""
        for stages, mbs, virtual in ((2, 2, 2), (2, 4, 2), (4, 4, 2),
                                     (2, 4, 4)):
            np.testing.assert_allclose(
                self._run("interleaved_1f1b", stages, mbs, virtual),
                self._run("cond", stages, mbs),
                rtol=1e-6,
            )

    def test_interleaved_1f1b_dropout(self, devices8):
        """Dropout under interleaved_1f1b (cond fwd is safe inside
        custom_vjp; rng streams keyed by (microbatch, global layer))
        must match the dense AD schedule exactly."""
        a = self._run("interleaved_1f1b", 2, 4, 2, dropout=0.1)
        b = self._run("dense", 2, 4, dropout=0.1)
        np.testing.assert_allclose(a, b, rtol=1e-6)
