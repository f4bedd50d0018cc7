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
from repro.models.moe import moe_ffn, moe_init


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


def _dense_moe(x, p, top_k, first=0, norm=True):
    """Every held expert on every token, weighted by its picks: the plain
    form of the dropless held-share layer."""
    xf = x.reshape(-1, x.shape[-1])
    probs = jax.nn.softmax(xf @ p["router"], axis=-1)
    w, idx = jax.lax.top_k(probs, top_k)
    if norm:
        w = w / w.sum(-1, keepdims=True)
    y = jnp.zeros_like(xf)
    for e in range(p["w_gate"].shape[0]):
        h = jax.nn.silu(xf @ p["w_gate"][e]) * (xf @ p["w_up"][e])
        y = y + jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)[:, None] * (
            h @ p["w_down"][e])
    return y.reshape(x.shape)


class TestMoERouting:
    def test_moe_output_impl_invariant(self, rng):
        """Routing is per token: a token's output is the same whether it is
        computed in a (2, 8) block or alone."""
        key = jax.random.PRNGKey(0)
        p = moe_init(key, 16, 32, 4, dtype=jnp.float32)
        x = jnp.asarray(rng.standard_normal((2, 8, 16)).astype(np.float32))
        a, _ = moe_ffn(x, p, top_k=2)
        b = jnp.stack([jnp.concatenate([moe_ffn(x[i:i + 1, j:j + 1], p, top_k=2)[0]
                                        for j in range(8)], axis=1)[0]
                       for i in range(2)])
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)

    def test_capacity_drops_tokens(self, rng):
        """No capacity: with every token routed to the same two experts,
        each still gets its full weighted expert output (nothing dropped),
        as the plain every-expert form computes it."""
        key = jax.random.PRNGKey(0)
        p = moe_init(key, 8, 16, 2, dtype=jnp.float32)
        x = jnp.asarray(rng.standard_normal((1, 32, 8)).astype(np.float32))
        out, picks = moe_ffn(x, p, top_k=2)
        assert np.all(np.sort(np.asarray(picks), -1) == [0, 1])
        np.testing.assert_allclose(np.asarray(out), np.asarray(_dense_moe(x, p, 2)),
                                   rtol=1e-5, atol=1e-5)

    def test_held_share_matches_plain_form(self, rng):
        """Holding experts 2-4 of 6 (no renormalisation), the grouped
        matmul over the held picks equals the plain every-held-expert form,
        and the picks name the held expert (0-2 for ids 2-4, 3 elsewhere)."""
        key = jax.random.PRNGKey(1)
        full = moe_init(key, 8, 16, 6, dtype=jnp.float32)
        p = dict(full, w_gate=full["w_gate"][2:5], w_up=full["w_up"][2:5],
                 w_down=full["w_down"][2:5])
        x = jnp.asarray(rng.standard_normal((2, 5, 8)).astype(np.float32))
        out, picks = moe_ffn(x, p, top_k=3, first=2, norm_topk_prob=False)
        want = _dense_moe(x, p, 3, first=2, norm=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        _, idx = jax.lax.top_k(jax.nn.softmax(x.reshape(10, 8) @ p["router"]), 3)
        idx = np.asarray(idx)
        np.testing.assert_array_equal(np.asarray(picks).reshape(10, 3),
                                      np.where((idx >= 2) & (idx < 5), idx - 2, 3))

    def test_moe_grads(self, rng):
        key = jax.random.PRNGKey(0)
        p = moe_init(key, 8, 16, 4, dtype=jnp.float32)
        x = jnp.asarray(rng.standard_normal((1, 8, 8)).astype(np.float32))

        def loss(p):
            return jnp.sum(moe_ffn(x, p, top_k=2)[0] ** 2)

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
