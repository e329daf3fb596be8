"""End-to-end system tests: training loop with resume, serving driver,
VGG-16 sparse pipeline, HLO analyzer fidelity."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.vscnn_vgg16 import CONFIG as VGGCFG
from repro.launch.train import TrainLoop
from repro.launch.serve import Request, Server
from repro.models.cnn import (
    collect_conv_traffic, sparsify_vgg16, vgg16_apply, vgg16_schema,
)
from repro.models.layers import init_params


class TestTrainLoop:
    def test_loss_decreases_and_resumes(self):
        cfg = get_config("qwen1.5-4b").reduce()
        with tempfile.TemporaryDirectory() as d:
            ck = os.path.join(d, "ckpt")
            loop = TrainLoop(cfg, batch=4, seq=32, ckpt_dir=ck, ckpt_every=5)
            _, _, hist = loop.run(8, log_every=100)
            # fresh batch per step + lr warmup: assert stability, not descent
            # (per-arch descent on a fixed batch is covered in models smoke)
            assert all(np.isfinite(hist))
            assert max(hist) - min(hist) < 1.0
            # resume: a new loop continues from the saved step
            loop2 = TrainLoop(cfg, batch=4, seq=32, ckpt_dir=ck, ckpt_every=5)
            params, opt_state, start = loop2.maybe_resume()
            assert start == 8
            _, _, hist2 = loop2.run(10, log_every=100)
            assert len(hist2) == 2  # steps 8..9 only

    def test_straggler_monitor(self):
        from repro.launch.train import StragglerMonitor
        mon = StragglerMonitor(window=8, factor=3.0)
        for _ in range(10):
            assert not mon.observe(0.1)
        assert mon.observe(1.0)
        assert mon.events == 1

    def test_moe_arch_trains(self):
        cfg = get_config("granite-moe-3b-a800m").reduce()
        loop = TrainLoop(cfg, batch=4, seq=32, ckpt_dir=None)
        _, _, hist = loop.run(4, log_every=100)
        assert all(np.isfinite(hist))
        assert max(hist) - min(hist) < 1.0


class TestServer:
    def test_batched_serving(self):
        cfg = get_config("rwkv6-3b").reduce()
        rng = np.random.default_rng(0)
        reqs = [Request(rid=i,
                        prompt=rng.integers(0, cfg.vocab, 12, dtype=np.int32),
                        max_new=6)
                for i in range(5)]
        srv = Server(cfg, batch=4, capacity=32)
        stats = srv.serve(reqs)
        # continuous batching: the fifth request backfills a retired slot,
        # so one run serves all five (was 2 runs pre-backfill)
        assert len(stats) == 1
        s = stats[0]
        assert s["backfills"] == 1 and s["finished"] == 5
        # first wave: prefill + 5 decodes; backfilled request: 5 more
        assert s["decode_steps"] == 10
        assert all(len(r.out) == 6 for r in reqs)
        assert s["new_tokens"] == 30


class TestVGGPipeline:
    def test_sparse_paths_agree_with_pruned_dense(self):
        cfg = VGGCFG.reduce()
        key = jax.random.PRNGKey(0)
        params = init_params(
            vgg16_schema(cfg.num_classes, image_size=cfg.image_size),
            key, jnp.float32)
        x = jax.random.normal(key, (2, cfg.image_size, cfg.image_size, 3))
        sparse, pruned = sparsify_vgg16(params, cfg.weight_density,
                                        vk=cfg.vk, vn=cfg.vn)
        ref = vgg16_apply(pruned, x)
        out = vgg16_apply(params, x, sparse=sparse, impl="jnp")
        rel = (np.abs(np.asarray(out) - np.asarray(ref)).max()
               / np.abs(np.asarray(ref)).max())
        assert rel < 1e-4

    def test_traffic_collection_layer_count(self):
        cfg = VGGCFG.reduce()
        params = init_params(
            vgg16_schema(cfg.num_classes, image_size=cfg.image_size),
            jax.random.PRNGKey(0), jnp.float32)
        x = jnp.ones((1, cfg.image_size, cfg.image_size, 3))
        rec = collect_conv_traffic(params, x)
        assert len(rec) == 13  # VGG-16 conv layers

    def test_activation_sparsity_exists_after_relu(self):
        """The paper's input-side skipping depends on post-ReLU zeros."""
        cfg = VGGCFG.reduce()
        params = init_params(
            vgg16_schema(cfg.num_classes, image_size=cfg.image_size),
            jax.random.PRNGKey(0), jnp.float32)
        from repro.data import SyntheticImages
        img = SyntheticImages(1, size=cfg.image_size).batch_at(0)["images"]
        rec = collect_conv_traffic(params, jnp.asarray(img))
        # deeper conv inputs are post-ReLU: a solid fraction must be zeros
        densities = [float((np.asarray(x) != 0).mean()) for _, x, _ in rec[1:]]
        assert min(densities) < 0.9


def _xla_flops(compiled) -> float:
    return compiled.cost_analysis()["flops"]


class TestHloAnalyzer:
    def test_matches_xla_cost_analysis_loop_free(self, rng):
        """For a while-free program our FLOP count must match XLA's."""
        from repro.utils.hlo import analyze

        def f(a, b):
            return (a @ b).sum()

        a = jnp.asarray(rng.standard_normal((64, 128)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((128, 32)), jnp.float32)
        compiled = jax.jit(f).lower(a, b).compile()
        got = analyze(compiled.as_text()).flops
        want = _xla_flops(compiled)
        assert got == pytest.approx(want, rel=0.05)

    def test_while_trip_multiplication(self, rng):
        from repro.utils.hlo import analyze

        def f(x, w):
            def body(h, _):
                return h @ w, ()
            h, _ = jax.lax.scan(body, x, None, length=7)
            return h.sum()

        x = jnp.asarray(rng.standard_normal((32, 32)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((32, 32)), jnp.float32)
        compiled = jax.jit(f).lower(x, w).compile()
        got = analyze(compiled.as_text()).flops
        body_once = _xla_flops(compiled)
        assert got >= 6 * body_once  # trip count applied (XLA counts once)
        assert got == pytest.approx(7 * 2 * 32 * 32 * 32, rel=0.1)
