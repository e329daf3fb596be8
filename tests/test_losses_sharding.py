"""Chunked vocab-sharded CE vs dense oracle; MeshRules spec derivation."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as PS

from repro.launch.mesh import auto_mesh, make_local_mesh
from repro.parallel import sharding as shd
from repro.parallel.losses import chunked_cross_entropy, cross_entropy_dense


class TestChunkedCE:
    @pytest.mark.parametrize("t,chunk", [(16, 4), (16, 16), (15, 4)])
    def test_matches_dense(self, t, chunk, rng):
        b, d, v = 3, 8, 32
        h = jnp.asarray(rng.standard_normal((b, t, d)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((d, v)), jnp.float32)
        labels = jnp.asarray(rng.integers(0, v, (b, t)), jnp.int32)
        got = chunked_cross_entropy(h, labels, w, real_vocab=v, chunk=chunk)
        ref = cross_entropy_dense(jnp.einsum("btd,dv->btv", h, w), labels)
        assert abs(float(got) - float(ref)) < 1e-4

    def test_padded_vocab_masked(self, rng):
        b, t, d, v, vp = 2, 8, 8, 30, 32
        h = jnp.asarray(rng.standard_normal((b, t, d)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((d, vp)), jnp.float32)
        # put huge weight on padded columns; they must not affect the loss
        w = w.at[:, v:].set(100.0)
        labels = jnp.asarray(rng.integers(0, v, (b, t)), jnp.int32)
        got = chunked_cross_entropy(h, labels, w, real_vocab=v)
        ref = cross_entropy_dense(
            jnp.einsum("btd,dv->btv", h, w[:, :v]), labels)
        assert abs(float(got) - float(ref)) < 1e-4

    def test_z_loss_positive(self, rng):
        b, t, d, v = 2, 8, 8, 32
        h = jnp.asarray(10 * rng.standard_normal((b, t, d)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((d, v)), jnp.float32)
        labels = jnp.asarray(rng.integers(0, v, (b, t)), jnp.int32)
        base = chunked_cross_entropy(h, labels, w, real_vocab=v)
        with_z = chunked_cross_entropy(h, labels, w, real_vocab=v,
                                       z_weight=1e-2)
        assert float(with_z) > float(base)

    def test_mask_excludes_positions(self, rng):
        b, t, d, v = 2, 8, 8, 32
        h = jnp.asarray(rng.standard_normal((b, t, d)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((d, v)), jnp.float32)
        labels = jnp.asarray(rng.integers(0, v, (b, t)), jnp.int32)
        mask = jnp.zeros((b, t), bool).at[:, :4].set(True)
        got = chunked_cross_entropy(h, labels, w, real_vocab=v, mask=mask)
        ref = chunked_cross_entropy(h[:, :4], labels[:, :4], w, real_vocab=v)
        assert abs(float(got) - float(ref)) < 1e-4


class TestMeshRules:
    def _mesh(self):
        return make_local_mesh(data=1, model=1)

    def test_spec_demotes_non_divisible(self):
        mesh = auto_mesh((1, 1), ("data", "model"))
        rules = shd.TRAIN_RULES
        # 8 kv heads over 16-way model axis would not divide on the real
        # mesh; emulate with a shape check against a fake axis size via the
        # real mesh (1 divides everything -> stays)
        spec = shd.spec_for(("batch", "kv_heads"), mesh=mesh, rules=rules,
                            shape=(4, 8))
        assert spec == PS("data", "model")

    def test_missing_axis_filtered(self):
        mesh = auto_mesh((1, 1), ("data", "model"))
        spec = shd.spec_for(("batch",), mesh=mesh, rules=shd.TRAIN_RULES,
                            shape=(8,))
        # batch maps to ('pod','data'); 'pod' absent from this mesh
        assert spec == PS("data")

    def test_repeated_axis_demoted(self):
        mesh = auto_mesh((1, 1), ("data", "model"))
        spec = shd.spec_for(("heads", "ff"), mesh=mesh, rules=shd.TRAIN_RULES,
                            shape=(4, 4))
        # both want 'model'; the second claim loses
        assert spec == PS("model", None)

    def test_divisibility_guard(self):
        # AbstractMesh: spec_for only consults mesh.shape (no devices needed)
        mesh = jax.sharding.AbstractMesh((1, 2), ("data", "model"))
        spec = shd.spec_for(("ff",), mesh=mesh, rules=shd.TRAIN_RULES,
                            shape=(7,))  # 7 % 2 != 0 -> replicate
        assert spec == PS(None)
        spec2 = shd.spec_for(("ff",), mesh=mesh, rules=shd.TRAIN_RULES,
                             shape=(8,))
        assert spec2 == PS("model")

    def test_kv_heads_demoted_on_16way_axis(self):
        mesh = jax.sharding.AbstractMesh((16, 16), ("data", "model"))
        spec = shd.spec_for(("batch", None, "kv_heads", "head_dim"),
                            mesh=mesh, rules=shd.TRAIN_RULES,
                            shape=(256, 4096, 8, 128))
        assert spec == PS("data", None, None, None)  # 8 % 16 != 0

    def test_logical_noop_outside_mesh(self, rng):
        x = jnp.ones((4, 4))
        assert shd.logical(x, ("batch", None)) is x

    def test_constraint_applies_in_mesh(self):
        mesh = auto_mesh((1, 1), ("data", "model"))
        with shd.use_mesh(mesh, shd.TRAIN_RULES):
            y = shd.logical(jnp.ones((4, 4)), ("batch", "ff"))
            assert y.shape == (4, 4)
