"""Serving backends end to end: LM continuous batching (EOS retirement,
cache-merge backfill, exact decode-step accounting) and the batched CNN
path through `SparseNet.apply`.

The LM server is module-scoped: prefill/decode/merge jits compile once and
every test reuses them (eos_id is restored after mutation).
"""
import jax.numpy as jnp
import numpy as np
import pytest
from test_scheduler import StepOnly

from repro.configs import get_config
from repro.launch.scheduler import FleetScheduler
from repro.launch.serve import (
    CNNBackend, CNNServer, ImageRequest, Request, Server,
    random_prompt_lengths,
)
from repro.models import graph as G


@pytest.fixture(scope="module")
def lm_server():
    cfg = get_config("rwkv6-3b").reduce()
    # len_bucket=1: no length rounding, so tests control padding exactly
    return Server(cfg, batch=2, capacity=32, len_bucket=1)


def _reqs(cfg, lens_max_new, prompt_len=6, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab, prompt_len,
                                        dtype=np.int32),
                    max_new=mn)
            for i, mn in enumerate(lens_max_new)]


class TestLMServing:
    def test_exact_decode_steps(self, lm_server):
        """max_new tokens cost exactly max_new - 1 decodes (prefill emits
        the first) — the trailing-decode off-by-one regression pin."""
        reqs = _reqs(lm_server.cfg, [4, 4])
        stats = lm_server.serve(reqs)
        assert len(stats) == 1
        s = stats[0]
        assert s["decode_steps"] == 3
        assert s["new_tokens"] == 8
        assert all(len(r.out) == 4 for r in reqs)

    def test_retirement_frees_slot_for_queued_request(self, lm_server):
        """The headline regression: a short sequence retires mid-run and a
        queued request is backfilled into its slot in the same run — the
        run is bounded by the longest request, not the sum."""
        reqs = _reqs(lm_server.cfg, [2, 6, 3])
        stats = lm_server.serve(reqs)
        assert len(stats) == 1           # one run serves all three
        s = stats[0]
        assert s["backfills"] == 1 and s["finished"] == 3
        assert [len(r.out) for r in reqs] == [2, 6, 3]
        assert s["decode_steps"] == 5    # max(max_new) - 1
        assert s["new_tokens"] == 11

    def test_eos_retirement(self, lm_server):
        """A sequence retires the moment it emits eos_id, not at max_new."""
        [probe] = _reqs(lm_server.cfg, [6], seed=3)
        lm_server.serve([probe])
        assert len(probe.out) == 6
        eos = probe.out[1]               # greedy decode is deterministic
        [req] = _reqs(lm_server.cfg, [6], seed=3)
        lm_server.backend.eos_id = eos
        try:
            stats = lm_server.serve([req])
        finally:
            lm_server.backend.eos_id = None
        assert req.out == probe.out[:2]  # eos recorded, then retired
        assert stats[0]["decode_steps"] == 1

    def test_backfill_cache_merge_parity(self, lm_server):
        """A backfilled request must compute exactly what the same request
        computes when served alone at that context length — pins the
        prefill-and-merge cache scatter."""
        cfg = lm_server.cfg
        a, b = _reqs(cfg, [2, 3], seed=5)
        one = Server(cfg, batch=1, capacity=32, len_bucket=1)
        stats = one.serve([a, b])
        assert stats[0]["backfills"] == 1
        # b backfilled into slot 0 at context length 6+1: serve it alone,
        # left-padded to the same length, on the same width-1 jits
        b2 = Request(rid=9,
                     prompt=np.concatenate([np.zeros(1, np.int32),
                                            np.asarray(b.prompt)]),
                     max_new=3)
        one.serve([b2])
        assert b2.out == b.out

    def test_run_batch_overflow_backfills(self, lm_server):
        """run_batch with more requests than slots serves them all via
        backfill instead of silently dropping."""
        reqs = _reqs(lm_server.cfg, [2, 2, 2])
        s = lm_server.run_batch(reqs)
        assert s["finished"] == 3 and s["backfills"] == 1
        assert all(len(r.out) == 2 for r in reqs)

    def test_run_batch_raises_on_unservable_request(self, lm_server):
        """A request that can never join the run (token budget would
        overflow capacity) surfaces as an error, not a silent drop."""
        # the third request only fits via backfill, but 30 new tokens would
        # overflow capacity 32 from any retirement point
        reqs = _reqs(lm_server.cfg, [2, 2, 30])
        with pytest.raises(ValueError, match="could not backfill"):
            lm_server.run_batch(reqs)

    def test_backfill_prefill_shape_bucketing(self):
        """Backfills at distinct retirement steps share one bucketed
        prefill executable (the recompile-storm fix): the context is
        right-padded to the len_bucket ladder and the first token read at
        the true position, so the jit cache holds one entry, not one per
        distinct context length."""
        cfg = get_config("qwen1.5-4b").reduce()   # attention KV caches
        srv = Server(cfg, batch=2, capacity=64, len_bucket=8)
        assert srv.backend.backfill_bucket == 8
        reqs = _reqs(cfg, [2, 8, 3, 4], seed=7)
        stats = srv.serve(reqs)
        assert len(stats) == 1
        assert stats[0]["backfills"] == 2        # at two distinct steps
        assert [len(r.out) for r in reqs] == [2, 8, 3, 4]
        # both backfill contexts (9 and 11) round to the same 16-bucket
        assert srv.backend._prefill_at._cache_size() == 1

    def test_bucketed_backfill_matches_exact(self):
        """Right-padding the backfill context to the bucket must not change
        a single emitted token vs the exact-length prefill (junk K/V rows
        are masked, then overwritten by the next decode steps)."""
        cfg = get_config("qwen1.5-4b").reduce()
        outs = []
        for bucket in (8, 1):                    # bucketed vs exact
            srv = Server(cfg, batch=2, capacity=64, len_bucket=8)
            srv.backend.backfill_bucket = bucket
            reqs = _reqs(cfg, [2, 8, 3, 4], seed=7)
            stats = srv.serve(reqs)
            assert stats[0]["backfills"] == 2
            outs.append([r.out for r in reqs])
        assert outs[0] == outs[1]

    def test_stateful_caches_keep_exact_backfill(self):
        """rwkv state caches fold in every processed token, and
        sliding-window K/V caches are circular (right-pad junk would wrap
        onto real in-window history): both keep the exact-length backfill
        (bucket 1) even when admission buckets lengths."""
        for arch in ("rwkv6-3b", "gemma3-12b"):   # recurrent / windowed
            cfg = get_config(arch).reduce()
            srv = Server(cfg, batch=1, capacity=32, len_bucket=16)
            assert srv.backend.backfill_bucket == 1, arch

    def test_modality_dispatch_fields(self):
        assert get_config("rwkv6-3b").modality == "lm"
        assert get_config("vscnn-vgg16").modality == "cnn"
        assert get_config("vscnn-resnet18").modality == "cnn"

    def test_prompt_len_validation(self):
        """--prompt-len 8 used to crash on rng.integers(8, 8)."""
        rng = np.random.default_rng(0)
        lens = random_prompt_lengths(rng, 20, 8)
        assert all(1 <= n < 8 for n in lens)
        lens = random_prompt_lengths(rng, 20, 2)
        assert all(n == 1 for n in lens)
        with pytest.raises(ValueError, match="prompt-len"):
            random_prompt_lengths(rng, 4, 1)


class TestCNNServing:
    def test_vgg_batched_serving_parity(self):
        """A mixed queue through SparseNet.apply with batch reuse: one
        run, a backfilled fifth image, outputs matching the
        direct batched apply."""
        cfg = get_config("vscnn-vgg16").reduce()
        srv = CNNServer(cfg, batch=4, seed=0)
        rng = np.random.default_rng(1)
        imgs = [rng.standard_normal((32, 32, 3)).astype(np.float32)
                for _ in range(5)]
        reqs = [ImageRequest(rid=i, image=im) for i, im in enumerate(imgs)]
        stats = srv.serve(reqs)
        assert len(stats) == 1
        s = stats[0]
        assert s["steps"] == 2           # wave of 4, then the backfilled 1
        assert s["backfills"] == 1 and s["finished"] == 5
        # one executable for the full wave + one for the shrunk final wave
        # (width 1) — the zero-pad lanes are no longer computed
        assert s["compiles"] == 2
        ref = np.asarray(G.net_apply(
            srv.net, srv.params, jnp.asarray(np.stack(imgs)),
            sparse=srv.sparse, impl="jnp"))
        for i, r in enumerate(reqs):
            assert r.logits is not None and r.logits.shape == (16,)
            np.testing.assert_allclose(r.logits, ref[i], rtol=1e-3,
                                       atol=1e-3)
            assert r.out == [int(ref[i].argmax())]

    def test_resnet_shape_buckets(self):
        """A size-agnostic net serves mixed image sizes as separate shape
        buckets, padding within each."""
        cfg = get_config("vscnn-resnet18").reduce()
        srv = CNNServer(cfg, batch=2, density=0.5, seed=0)
        rng = np.random.default_rng(2)
        reqs = [ImageRequest(rid=i,
                             image=rng.standard_normal((s, s, 3))
                                      .astype(np.float32))
                for i, s in enumerate([16, 24, 16])]
        stats = srv.serve(reqs)
        assert len(stats) == 2           # buckets (16,16,3) and (24,24,3)
        assert sum(s["finished"] for s in stats) == 3
        assert all(len(r.out) == 1 for r in reqs)
        assert srv.backend.apply.compiles == 2

    def test_final_wave_shrinks_to_occupied_slots(self):
        """A partial wave computes on a batch shrunk to the occupied slots
        (pow2 ladder), not the full width padded with zero images."""
        cfg = get_config("vscnn-vgg16").reduce()
        srv = CNNServer(cfg, batch=4, seed=0)
        rng = np.random.default_rng(4)
        reqs = [ImageRequest(rid=i,
                             image=rng.standard_normal((32, 32, 3))
                                      .astype(np.float32))
                for i in range(7)]
        stats = srv.serve(reqs)
        assert sum(s["finished"] for s in stats) == 7
        # wave of 4, then 3 backfills -> a 3-occupied wave on a width-4
        # batch: the pow2 ladder reuses the full-width executable
        widths = {k[-1][0] for k in srv.backend.apply.cache}
        assert widths == {4}
        # a lone trailing image lands on a width-1 executable
        srv.serve([ImageRequest(
            rid=9, image=rng.standard_normal((32, 32, 3))
                            .astype(np.float32))])
        widths = {k[-1][0] for k in srv.backend.apply.cache}
        assert widths == {4, 1}

    def test_fixed_input_rejects_oversize(self):
        """An oversize image for a fixed-input net is refused at admission
        with a structured outcome — it never reaches a batch (and never
        takes down the serve)."""
        cfg = get_config("vscnn-vgg16").reduce()   # image_size 32
        srv = CNNServer(cfg, batch=2, seed=0)
        big = ImageRequest(rid=0, image=np.zeros((48, 48, 3), np.float32))
        stats = srv.serve([big])
        assert stats == []
        assert big.outcome.status == "refused"
        assert big.outcome.reason.startswith("invalid:oversize")
        assert srv.outcomes[0] is big.outcome

    def test_malformed_requests_refused(self):
        """Every malformed-input arm becomes a structured refusal, and
        valid neighbors in the same serve still get answers."""
        cfg = get_config("vscnn-vgg16").reduce()
        srv = CNNServer(cfg, batch=2, seed=0)
        s = cfg.image_size
        good = ImageRequest(
            rid=0, image=np.ones((s, s, 3), np.float32))
        bad = [
            ImageRequest(rid=1, image=[[1.0]]),                # not ndarray
            ImageRequest(rid=2, image=np.ones((s, s), np.float32)),
            ImageRequest(rid=3, image=np.ones((s, s, 3), np.int32)),
            ImageRequest(rid=4, image=np.full((s, s, 3), np.nan,
                                              np.float32)),
        ]
        srv.serve([good] + bad)
        assert good.outcome.status == "delivered"
        assert good.out  # got a class
        reasons = [r.outcome.reason for r in bad]
        assert reasons[0].startswith("invalid:not_an_array")
        assert reasons[1].startswith("invalid:bad_rank")
        assert reasons[2].startswith("invalid:bad_dtype")
        assert reasons[3] == "invalid:non_finite_input"

    def test_lm_malformed_requests_refused(self, lm_server):
        """LM arm: empty prompts, wrong dtype/rank, bad budgets and
        over-capacity prompts are refused at admission; the valid request
        in the same serve still completes."""
        srv = lm_server
        good = Request(rid=0, prompt=np.arange(4, dtype=np.int32),
                       max_new=2)
        bad = [
            Request(rid=1, prompt=np.zeros(0, np.int32), max_new=2),
            Request(rid=2, prompt=np.ones(4, np.float32), max_new=2),
            Request(rid=3, prompt=np.ones((2, 2), np.int32), max_new=2),
            Request(rid=4, prompt=np.arange(4, dtype=np.int32), max_new=0),
            Request(rid=5, prompt=np.arange(100, dtype=np.int32),
                    max_new=2),   # capacity 32
        ]
        srv.serve([good] + bad)
        assert good.outcome.status == "delivered"
        assert len(good.out) == 2
        reasons = {r.rid: r.outcome.reason for r in bad}
        assert reasons[1] == "invalid:empty_prompt"
        assert reasons[2].startswith("invalid:bad_dtype")
        assert reasons[3].startswith("invalid:bad_rank")
        assert reasons[4].startswith("invalid:bad_max_new")
        assert reasons[5].startswith("invalid:prompt_too_long")
        for r in bad:
            assert r.out == []

    def test_run_ahead_matches_dispatch_then_collect(self):
        """The server's scheduler runs one wave ahead of its collects; the
        same backend behind a wrapper that hides dispatch/collect
        collects each wave before it dispatches the next.  Over four
        waves, the last a partial one, both form the same batches and
        deliver bit-identical logits and outcomes."""
        cfg = get_config("vscnn-vgg16").reduce()
        rng = np.random.default_rng(6)
        imgs = [rng.standard_normal((32, 32, 3)).astype(np.float32)
                for _ in range(13)]
        srv = CNNServer(cfg, batch=4, seed=0)
        assert type(srv.scheduler) is FleetScheduler
        served = []
        for sched in (srv.scheduler,
                      FleetScheduler([StepOnly(srv.backend)], batch=4)):
            reqs = [ImageRequest(rid=i, image=im)
                    for i, im in enumerate(imgs)]
            stats = sched.serve(reqs)
            assert [(s["steps"], s["backfills"]) for s in stats] == [(4, 9)]
            served.append(reqs)
        for a, b in zip(*served):
            assert a.logits.tobytes() == b.logits.tobytes()
            assert a.out == b.out
            assert (a.outcome.status, a.outcome.reason, a.outcome.wave) == \
                (b.outcome.status, b.outcome.reason, b.outcome.wave)

    def test_one_replica_builds_a_plain_backend(self):
        """One replica without shard_fc serves a plain `CNNBackend` over
        the server's own params: no device copy, no mesh."""
        cfg = get_config("vscnn-vgg16").reduce()
        srv = CNNServer(cfg, batch=2, seed=0)
        assert type(srv.backend) is CNNBackend
        assert srv.backends == [srv.backend]
        assert srv.backend.apply.mesh is None
        assert srv.backend.apply.params is srv.params
        assert srv.scheduler.backends == [srv.backend]

    def test_lockstep_max_queue_sheds(self):
        """Bounded admission on a one-replica server: requests beyond the
        depth are shed with a queue_full refusal, the rest are served."""
        cfg = get_config("vscnn-vgg16").reduce()
        srv = CNNServer(cfg, batch=2, seed=0, max_queue=3)
        s = cfg.image_size
        reqs = [ImageRequest(rid=i, image=np.ones((s, s, 3), np.float32))
                for i in range(5)]
        srv.serve(reqs)
        statuses = [r.outcome.status for r in reqs]
        assert statuses == ["delivered"] * 3 + ["refused"] * 2
        assert all(r.outcome.reason == "queue_full" for r in reqs[3:])

    def test_dense_path_serves(self):
        """sparse=False routes the same scheduler through plain XLA convs —
        the bench_serving baseline."""
        cfg = get_config("vscnn-vgg16").reduce()
        srv = CNNServer(cfg, batch=2, sparse=False, seed=0)
        rng = np.random.default_rng(3)
        reqs = [ImageRequest(rid=i,
                             image=rng.standard_normal((32, 32, 3))
                                      .astype(np.float32))
                for i in range(2)]
        stats = srv.serve(reqs)
        assert stats[0]["finished"] == 2
        ref = np.asarray(G.net_apply(
            srv.net, srv.params,
            jnp.asarray(np.stack([r.image for r in reqs]))))
        np.testing.assert_allclose(
            np.stack([r.logits for r in reqs]), ref, rtol=1e-5, atol=1e-5)


def _cnn_queue(n, seed=1, size=32):
    rng = np.random.default_rng(seed)
    return [ImageRequest(rid=i,
                         image=rng.standard_normal((size, size, 3))
                                  .astype(np.float32))
            for i in range(n)]


class TestCNNFleet:
    """Replica fleet on a single device: data-parallel replicas are weight
    copies, so the bar is *bit-identical* logits to the legacy
    single-backend path — not allclose."""

    def test_three_replicas_bit_identical_to_one(self):
        cfg = get_config("vscnn-vgg16").reduce()
        solo = CNNServer(cfg, batch=2, seed=0)
        ref_reqs = _cnn_queue(10)
        solo.serve(ref_reqs)
        fleet = CNNServer(cfg, batch=2, seed=0, replicas=3)
        reqs = _cnn_queue(10)
        stats = fleet.serve(reqs)
        # every replica actually served work
        assert {s["replica"] for s in stats} == {0, 1, 2}
        for r, ref in zip(reqs, ref_reqs):
            np.testing.assert_array_equal(np.asarray(r.logits),
                                          np.asarray(ref.logits))
            assert r.out == ref.out

    def test_shard_fc_single_device_parity(self):
        """shard_fc on one device degenerates to a replicated mesh; the
        sharded compile path must still match the legacy path bit-exactly."""
        cfg = get_config("vscnn-vgg16").reduce()
        solo = CNNServer(cfg, batch=2, seed=0)
        ref_reqs = _cnn_queue(4, seed=6)
        solo.serve(ref_reqs)
        srv = CNNServer(cfg, batch=2, seed=0, shard_fc=True)
        assert len(srv.group.meshes) == 1
        reqs = _cnn_queue(4, seed=6)
        srv.serve(reqs)
        for r, ref in zip(reqs, ref_reqs):
            np.testing.assert_array_equal(np.asarray(r.logits),
                                          np.asarray(ref.logits))

    def test_fleet_multi_device_subprocess(self):
        """8 forced host devices: 4 replicas land on 4 distinct devices,
        shard_fc cout-shards the big FC heads over each replica's model
        axis, and logits stay bit-identical to the 1-replica serve."""
        import os
        import subprocess
        import sys
        prog = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np
from repro.configs import get_config
from repro.launch.serve import CNNServer, ImageRequest

assert jax.device_count() == 8
cfg = get_config("vscnn-vgg16").reduce()
def queue():
    rng = np.random.default_rng(1)
    return [ImageRequest(rid=i,
                         image=rng.standard_normal((32, 32, 3))
                                  .astype(np.float32))
            for i in range(8)]
solo = CNNServer(cfg, batch=2, seed=0)
ref = queue()
solo.serve(ref)
srv = CNNServer(cfg, batch=2, seed=0, replicas=4, shard_fc=True)
devs = {m.devices.flat[0] for m in srv.group.meshes}
assert len(devs) == 4, devs                     # distinct replica devices
shards = {e.vs.vals.sharding.spec for e in srv.backend.apply.sparse.values()
          if type(e).__name__ == "SparseFC" and e.vs.vals.shape[0] > 1}
assert jax.sharding.PartitionSpec("model", None, None, None) in shards
reqs = queue()
stats = srv.serve(reqs)
assert {s["replica"] for s in stats} == {0, 1, 2, 3}
for r, x in zip(reqs, ref):
    np.testing.assert_array_equal(np.asarray(r.logits),
                                  np.asarray(x.logits))
print("FLEET-OK")
"""
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        r = subprocess.run(
            [sys.executable, "-c", prog], capture_output=True, text=True,
            timeout=600, cwd=root,
            env={**os.environ, "PYTHONPATH": os.path.join(root, "src")})
        assert r.returncode == 0, r.stderr[-4000:]
        assert "FLEET-OK" in r.stdout


class TestLMSampling:
    """Per-request temperature / top-k through the LM backend."""

    def _sreqs(self, cfg, specs, seed=11):
        rng = np.random.default_rng(seed)
        return [Request(rid=100 + i,
                        prompt=rng.integers(0, cfg.vocab, 6, dtype=np.int32),
                        max_new=mn, temperature=t, top_k=k)
                for i, (mn, t, k) in enumerate(specs)]

    def test_temperature_zero_is_greedy_bit_exact(self, lm_server):
        """temp=0 requests take the exact legacy greedy path — same tokens,
        whether top_k is set or not."""
        cfg = lm_server.cfg
        ref = self._sreqs(cfg, [(5, 0.0, 0), (5, 0.0, 0)])
        lm_server.serve(ref)
        got = self._sreqs(cfg, [(5, 0.0, 7), (5, 0.0, 3)])
        lm_server.serve(got)
        assert [r.out for r in got] == [r.out for r in ref]

    def test_top_k_one_matches_greedy(self, lm_server):
        """top_k=1 leaves only the argmax in the distribution, so any
        temperature still reproduces greedy decoding."""
        cfg = lm_server.cfg
        ref = self._sreqs(cfg, [(5, 0.0, 0)])
        lm_server.serve(ref)
        got = self._sreqs(cfg, [(5, 1.5, 1)])
        lm_server.serve(got)
        assert got[0].out == ref[0].out

    def test_sampling_reproducible_and_not_greedy(self, lm_server):
        """Sampled streams are keyed by (seed, rid, step): the same request
        re-served emits the same tokens, and a hot temperature actually
        leaves the greedy path."""
        cfg = lm_server.cfg
        a = self._sreqs(cfg, [(8, 5.0, 0)])
        lm_server.serve(a)
        b = self._sreqs(cfg, [(8, 5.0, 0)])
        lm_server.serve(b)
        assert a[0].out == b[0].out
        greedy = self._sreqs(cfg, [(8, 0.0, 0)])
        lm_server.serve(greedy)
        assert a[0].out != greedy[0].out

    def test_mixed_batch_keeps_greedy_lane_bit_exact(self, lm_server):
        """A sampled neighbour in the batch must not perturb a greedy
        lane's tokens (the `where(temp > 0, ...)` lane isolation)."""
        cfg = lm_server.cfg
        ref = self._sreqs(cfg, [(6, 0.0, 0), (6, 0.0, 0)])
        lm_server.serve(ref)
        mixed = self._sreqs(cfg, [(6, 0.0, 0), (6, 2.0, 20)])
        lm_server.serve(mixed)
        assert mixed[0].out == ref[0].out
