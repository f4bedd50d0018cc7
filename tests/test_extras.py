"""Extended coverage: RMSNorm kernel, MoE routing, sharded-vocab CE loss,
activation-sharding policy, hypothesis sweep on attention fusion."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import given, settings, st  # optional dep: skips when absent

from repro.kernels import ops, ref
from repro.kernels.rms_norm import rms_norm_pallas
from repro.models import losses
from repro.models.moe import _positions_onehot, _positions_sort, moe_ffn, moe_init


class TestRMSNormKernel:
    @pytest.mark.parametrize("shape", [(4, 64), (2, 16, 128), (3, 5, 32)])
    def test_matches_ref(self, rng, shape):
        x = rng.standard_normal(shape).astype(np.float32)
        w = rng.standard_normal(shape[-1:]).astype(np.float32)
        out = rms_norm_pallas(x, w, interpret=True, block_rows=2)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref.rms_norm_ref(x, w)),
            rtol=1e-5, atol=1e-6,
        )

    def test_matches_model_layer(self, rng):
        from repro.models.layers import rms_norm

        x = rng.standard_normal((4, 64)).astype(np.float32)
        w = rng.standard_normal((64,)).astype(np.float32)
        np.testing.assert_allclose(
            np.asarray(rms_norm_pallas(x, w, interpret=True)),
            np.asarray(rms_norm(jnp.asarray(x), jnp.asarray(w))),
            rtol=1e-5, atol=1e-6,
        )

    def test_ops_dispatch(self, rng):
        x = rng.standard_normal((8, 32)).astype(np.float32)
        w = np.ones((32,), np.float32)
        a = ops.rms_norm(x, w, impl="interpret")
        b = ops.rms_norm(x, w, impl="xla")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


class TestMoERouting:
    @given(st.integers(2, 12), st.integers(10, 200), st.integers(0, 2**31))
    @settings(max_examples=40, deadline=None)
    def test_sort_equals_onehot(self, n_experts, n, seed):
        rng = np.random.default_rng(seed)
        e = jnp.asarray(rng.integers(0, n_experts, n), jnp.int32)
        np.testing.assert_array_equal(
            np.asarray(_positions_sort(e, n_experts)),
            np.asarray(_positions_onehot(e, n_experts)),
        )

    def test_moe_output_impl_invariant(self, rng):
        key = jax.random.PRNGKey(0)
        p = moe_init(key, 16, 32, 4, dtype=jnp.float32)
        x = jnp.asarray(rng.standard_normal((2, 8, 16)).astype(np.float32))
        a = moe_ffn(x, p, n_experts=4, top_k=2, position_impl="sort")
        b = moe_ffn(x, p, n_experts=4, top_k=2, position_impl="onehot")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)

    def test_capacity_drops_tokens(self, rng):
        """Tiny capacity factor must drop (not crash) overflow tokens."""
        key = jax.random.PRNGKey(0)
        p = moe_init(key, 8, 16, 2, dtype=jnp.float32)
        x = jnp.asarray(rng.standard_normal((1, 32, 8)).astype(np.float32))
        out = moe_ffn(x, p, n_experts=2, top_k=2, capacity_factor=0.25)
        assert np.all(np.isfinite(np.asarray(out)))

    def test_moe_grads(self, rng):
        key = jax.random.PRNGKey(0)
        p = moe_init(key, 8, 16, 4, dtype=jnp.float32)
        x = jnp.asarray(rng.standard_normal((1, 8, 8)).astype(np.float32))

        def loss(p):
            return jnp.sum(moe_ffn(x, p, n_experts=4, top_k=2) ** 2)

        g = jax.grad(loss)(p)
        assert all(np.all(np.isfinite(np.asarray(l, np.float32)))
                   for l in jax.tree_util.tree_leaves(g))


class TestShardedVocabLoss:
    def test_matches_naive(self, rng):
        logits = jnp.asarray(rng.standard_normal((4, 16, 33)).astype(np.float32))
        labels = jnp.asarray(rng.integers(0, 33, (4, 16)), jnp.int32)
        ours = losses.cross_entropy(logits, labels)
        # naive reference
        logp = jax.nn.log_softmax(logits, axis=-1)
        naive = -jnp.mean(
            jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]
        )
        np.testing.assert_allclose(float(ours), float(naive), rtol=1e-6)

    def test_ignore_id(self, rng):
        logits = jnp.asarray(rng.standard_normal((2, 8, 11)).astype(np.float32))
        labels = jnp.full((2, 8), -1, jnp.int32)
        labels = labels.at[0, 0].set(3)
        loss = losses.cross_entropy(logits, labels, ignore_id=-1)
        # only one token counts
        expect = losses.cross_entropy(logits[:1, :1], labels[:1, :1])
        np.testing.assert_allclose(float(loss), float(expect), rtol=1e-6)

    def test_grad_is_softmax_minus_onehot(self, rng):
        logits = jnp.asarray(rng.standard_normal((1, 4, 7)).astype(np.float32))
        labels = jnp.asarray(rng.integers(0, 7, (1, 4)), jnp.int32)
        g = jax.grad(lambda l: losses.cross_entropy(l, labels))(logits)
        p = jax.nn.softmax(logits, -1)
        oh = jax.nn.one_hot(labels, 7)
        np.testing.assert_allclose(np.asarray(g), np.asarray((p - oh) / 4),
                                   rtol=1e-4, atol=1e-6)


class TestActivationPolicy:
    def test_noop_without_policy(self, rng):
        from repro.distrib.actsharding import constrain

        x = jnp.ones((4, 4))
        assert constrain(x, "heads") is x

    def test_policy_filters_kinds(self):
        from repro.distrib.actsharding import ActivationPolicy
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((1, 1), ("data", "model"))
        pol = ActivationPolicy(mesh=mesh, only=frozenset({"logits"}))
        assert pol.spec_for("heads", (2, 4, 8, 16)) is None
        assert pol.spec_for("logits", (2, 8, 512)) is not None

    def test_constrain_inside_jit(self):
        from repro.distrib.actsharding import ActivationPolicy, use_policy, constrain
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((1, 1), ("data", "model"))
        with use_policy(ActivationPolicy(mesh=mesh)):
            out = jax.jit(lambda x: constrain(x, "tokens") * 2)(
                jnp.ones((2, 4, 8))
            )
        np.testing.assert_allclose(np.asarray(out), 2.0)


class TestAttentionFusionProperty:
    @given(
        st.sampled_from([(1, 2, 1), (2, 4, 2), (1, 4, 4), (1, 8, 2)]),
        st.sampled_from([4, 8, 16]),
        st.sampled_from([8, 16]),
        st.booleans(),
        st.integers(0, 2**31),
    )
    @settings(max_examples=15, deadline=None)
    def test_fusion_preserves_semantics(self, bhk, S, D, causal, seed):
        """Random attention dims: fusion must fire and preserve values."""
        from repro.core.capture import graph_to_fn, trace_to_graph
        from repro.core.passes import run_forge_passes

        B, H, KVH = bhk
        rng = np.random.default_rng(seed)

        def f(q, k, v):
            from jax import lax

            grp = H // KVH
            k2 = jnp.broadcast_to(
                k[:, :, None], (B, KVH, grp, S, D)
            ).reshape(B, H, S, D) if grp > 1 else k
            v2 = jnp.broadcast_to(
                v[:, :, None], (B, KVH, grp, S, D)
            ).reshape(B, H, S, D) if grp > 1 else v
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k2,
                           preferred_element_type=jnp.float32)
            s = s * (1.0 / np.sqrt(D))
            if causal:
                row = lax.broadcasted_iota(jnp.int32, (S, S), 0)
                col = lax.broadcasted_iota(jnp.int32, (S, S), 1)
                s = jnp.where(row >= col, s,
                              jnp.asarray(jnp.finfo(s.dtype).min, s.dtype))
            p = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v2.dtype), v2)

        q = rng.standard_normal((B, H, S, D)).astype(np.float32) * 0.5
        k = rng.standard_normal((B, KVH, S, D)).astype(np.float32) * 0.5
        v = rng.standard_normal((B, KVH, S, D)).astype(np.float32) * 0.5
        g = trace_to_graph(f, q, k, v).graph
        expect = graph_to_fn(g)(q, k, v)[0]
        run_forge_passes(g)
        assert any(n.op == "forge.sdpa" for n in g.nodes.values())
        got = graph_to_fn(g)(q, k, v)[0]
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(expect, np.float32),
                                   rtol=1e-4, atol=1e-5)
