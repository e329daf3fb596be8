"""Optimizers, schedules, data pipeline, checkpointing, compression."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.data import LMBatchSpec, SyntheticImages, SyntheticLM
from repro.optim import adafactor, adamw, clip_by_global_norm, global_norm
from repro.optim.schedules import constant, warmup_cosine, warmup_linear
from repro.parallel.compression import (
    compressed_psum, dequantize_fp8_block, quantize_fp8_block,
)


class TestOptimizers:
    @pytest.mark.parametrize("make_opt", [adamw, adafactor])
    def test_minimizes_quadratic(self, make_opt):
        opt = make_opt()
        params = {"w": jnp.asarray([3.0, -2.0, 1.0])}
        state = opt.init(params)

        def loss(p):
            return jnp.sum(p["w"] ** 2)

        for step in range(200):
            g = jax.grad(loss)(params)
            upd, state = opt.update(g, state, params, jnp.float32(0.05))
            params = jax.tree.map(lambda a, u: a + u, params, upd)
        assert float(loss(params)) < 1e-2

    def test_adafactor_memory_factored(self):
        opt = adafactor(min_dim_factored=128)
        params = {"w": jnp.ones((256, 512)), "b": jnp.ones((4,))}
        st = opt.init(params)
        n = sum(x.size for x in jax.tree.leaves(st["moments"]))
        assert n == 256 + 512 + 4  # rows + cols for w, full for b

    def test_adamw_weight_decay_shrinks(self):
        opt = adamw(weight_decay=0.5)
        params = {"w": jnp.full((4,), 10.0)}
        st = opt.init(params)
        zero_g = {"w": jnp.zeros((4,))}
        upd, _ = opt.update(zero_g, st, params, jnp.float32(0.1))
        assert float(upd["w"].max()) < 0  # pure decay pulls toward zero

    def test_global_norm_clip(self):
        g = {"a": jnp.full((4,), 3.0), "b": jnp.full((3,), 4.0)}
        clipped, norm = clip_by_global_norm(g, 1.0)
        assert float(global_norm(clipped)) == pytest.approx(1.0, rel=1e-5)
        assert float(norm) == pytest.approx((9 * 4 + 16 * 3) ** 0.5, rel=1e-5)

    def test_schedules(self):
        lr = warmup_cosine(1.0, 10, 100)
        assert float(lr(0)) == 0.0
        assert float(lr(10)) == pytest.approx(1.0, rel=1e-3)
        assert float(lr(100)) == pytest.approx(0.1, rel=1e-2)
        lin = warmup_linear(1.0, 10, 110)
        assert float(lin(60)) == pytest.approx(0.5, rel=1e-2)
        assert float(constant(0.3)(999)) == pytest.approx(0.3)


class TestData:
    def test_deterministic_skip_to_step(self):
        spec = LMBatchSpec(global_batch=4, seq_len=64, vocab=1000)
        a = SyntheticLM(spec, seed=1).batch_at(17)
        b = SyntheticLM(spec, seed=1).batch_at(17)
        assert np.array_equal(a["tokens"], b["tokens"])

    def test_shards_differ(self):
        spec = lambda s: LMBatchSpec(global_batch=8, seq_len=64, vocab=1000,
                                     n_shards=2, shard=s)
        a = SyntheticLM(spec(0), seed=1).batch_at(3)
        b = SyntheticLM(spec(1), seed=1).batch_at(3)
        assert not np.array_equal(a["tokens"], b["tokens"])
        assert a["tokens"].shape == (4, 64)

    def test_labels_are_next_tokens(self):
        spec = LMBatchSpec(global_batch=2, seq_len=32, vocab=100)
        batch = SyntheticLM(spec, seed=0).batch_at(0)
        assert np.array_equal(batch["tokens"][:, 1:], batch["labels"][:, :-1])

    def test_images_have_relu_sparsity_structure(self):
        batch = SyntheticImages(2, size=64).batch_at(0)
        img = batch["images"]
        assert img.shape == (2, 64, 64, 3)
        assert abs(img.mean()) < 0.1 and 0.5 < img.std() < 2.0


class TestCheckpoint:
    def test_roundtrip_and_gc(self):
        with tempfile.TemporaryDirectory() as d:
            cm = CheckpointManager(d, keep=2, async_save=False)
            tree = {"w": jnp.arange(6.0).reshape(2, 3), "s": jnp.int32(7)}
            for s in (1, 2, 3):
                cm.save(s, tree)
            assert cm.all_steps() == [2, 3]  # keep=2 gc'd step 1
            target = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
            out, step, _ = cm.restore(target)
            assert step == 3
            assert np.array_equal(out["w"], np.arange(6.0).reshape(2, 3))

    def test_crash_safe_tmp_never_published(self):
        with tempfile.TemporaryDirectory() as d:
            cm = CheckpointManager(d, async_save=False)
            cm.save(5, {"x": jnp.ones(3)})
            # stray tmp dir (simulated crash) must not be listed as a step
            os.makedirs(os.path.join(d, ".tmp_step_9"))
            assert cm.all_steps() == [5]

    def test_shape_mismatch_rejected(self):
        with tempfile.TemporaryDirectory() as d:
            cm = CheckpointManager(d, async_save=False)
            cm.save(1, {"x": jnp.ones(3)})
            with pytest.raises(ValueError):
                cm.restore({"x": jax.ShapeDtypeStruct((4,), jnp.float32)})

    def test_async_save_visible_after_wait(self):
        with tempfile.TemporaryDirectory() as d:
            cm = CheckpointManager(d, async_save=True)
            cm.save(2, {"x": jnp.ones(3)})
            cm.wait()
            assert cm.all_steps() == [2]


class TestCompression:
    def test_fp8_roundtrip_error_bound(self, rng):
        x = jnp.asarray(rng.standard_normal(4096).astype(np.float32))
        q, s, pad = quantize_fp8_block(x, block=256)
        xr = dequantize_fp8_block(q, s, pad, x.shape)
        rel = float(jnp.abs(x - xr).max() / jnp.abs(x).max())
        assert rel < 0.1

    def test_outlier_blocks_isolated(self, rng):
        """Per-block scaling: an outlier ruins only its own block."""
        x = np.zeros(1024, np.float32)
        x[:512] = rng.standard_normal(512)
        x[600] = 1e4
        xq, s, pad = quantize_fp8_block(jnp.asarray(x), block=512)
        xr = np.asarray(dequantize_fp8_block(xq, s, pad, x.shape))
        assert np.abs(xr[:512] - x[:512]).max() < 0.05 * np.abs(x[:512]).max()

    def test_error_feedback_unbiased_over_steps(self, rng):
        """Repeated compression of the same gradient with EF: accumulated
        applied signal converges to the true signal (EF-SGD property)."""
        g = jnp.asarray(rng.standard_normal(512).astype(np.float32)) * 1e-3
        err = jnp.zeros_like(g)
        applied = jnp.zeros_like(g)
        for _ in range(20):
            target = g + err
            q, s, pad = quantize_fp8_block(target, block=128)
            deq = dequantize_fp8_block(q, s, pad, g.shape)
            err = target - deq
            applied = applied + deq
        # mean applied per step ~ g
        rel = float(jnp.abs(applied / 20 - g).max() / jnp.abs(g).max())
        assert rel < 0.05

    def test_compressed_psum_under_shard_map(self, rng):
        from jax.sharding import PartitionSpec as PS

        from repro.launch.mesh import auto_mesh
        mesh = auto_mesh((1,), ("pod",))
        x = jnp.asarray(rng.standard_normal(256).astype(np.float32))
        err = jnp.zeros_like(x)

        def body(xl, el):
            return compressed_psum(xl, "pod", el)

        y, new_err = jax.shard_map(body, mesh=mesh, in_specs=(PS(), PS()),
                                   out_specs=(PS(), PS()),
                                   check_vma=False)(x, err)
        rel = float(jnp.abs(y - x).max() / jnp.abs(x).max())
        assert rel < 0.1  # pod size 1: psum == dequantized identity


class TestAdamW8bit:
    def test_minimizes_quadratic(self):
        from repro.optim import adamw8bit
        opt = adamw8bit(weight_decay=0.0)
        params = {"w": jnp.asarray([3.0, -2.0, 1.0] * 100)}
        state = opt.init(params)
        loss = lambda p: jnp.sum(p["w"] ** 2)
        for _ in range(250):
            g = jax.grad(loss)(params)
            upd, state = opt.update(g, state, params, jnp.float32(0.05))
            params = jax.tree.map(lambda a, u: a + u, params, upd)
        assert float(loss(params)) < 1e-1

    def test_state_is_8bit(self):
        from repro.optim import adamw8bit
        opt = adamw8bit()
        params = {"w": jnp.ones((512, 512))}
        st = opt.init(params)
        mom = st["moments"]["w"]
        assert mom["mq"].dtype == jnp.int8 and mom["vq"].dtype == jnp.int8
        bits = (mom["mq"].size * 8 + mom["ms"].size * 32) / params["w"].size
        assert bits < 9  # ~8.125 bits/param/moment vs 32 for fp32

    def test_tracks_fp32_adamw(self):
        """A few steps of int8 AdamW stay close to exact AdamW."""
        from repro.optim import adamw, adamw8bit
        import numpy as np
        rng = np.random.default_rng(0)
        w0 = jnp.asarray(rng.standard_normal(1024), jnp.float32)
        paths = {}
        for name, opt in (("fp32", adamw(weight_decay=0.0)),
                          ("int8", adamw8bit(weight_decay=0.0))):
            p = {"w": w0}
            st = opt.init(p)
            for i in range(10):
                g = {"w": jnp.sin(p["w"] + i)}  # deterministic pseudo-grads
                upd, st = opt.update(g, st, p, jnp.float32(0.01))
                p = jax.tree.map(lambda a, u: a + u, p, upd)
            paths[name] = np.asarray(p["w"])
        drift = np.abs(paths["fp32"] - paths["int8"]).max()
        assert drift < 5e-3, drift
