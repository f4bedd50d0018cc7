"""Paged KV cache (ISSUE 6): page-pool / prefix-tree properties (no
double-free, refcounts match tree reachability, fork-then-free keeps
shared pages live), paged ≡ contiguous **bitwise** fidelity (decode,
batched prefill, GQA, window attention, mid-generation swap-in,
prefix-hit admission), and the Pallas paged-attention kernel against
its jnp reference in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import given, settings, st  # optional dep: skips when absent
from repro.configs import get_config
from repro.core.paging import (
    TRASH_PAGE,
    PagePool,
    PrefixTree,
    build_row_table,
    pages_for,
)
from repro.kernels.paged_attention import paged_attention
from repro.kernels.ref import gather_pages, paged_sdpa_ref
from repro.launch.serve import BatchedServer, Request, SlotScheduler
from repro.models import get_model
from repro.models.attention import attention, attn_init, make_cache


def _tokens(n, seed=0, vocab=512):
    rng = np.random.default_rng(seed)
    return rng.integers(0, vocab, (n,)).astype(np.int32)


# --------------------------------------------------------------------------
# PagePool properties
# --------------------------------------------------------------------------


class TestPagePool:
    def test_alloc_fork_free_refcounts(self):
        pool = PagePool(8, 4)
        a = pool.alloc(3)
        assert pool.pages_in_use == 4  # 3 + pinned trash
        assert all(pool.refcount(p) == 1 for p in a)
        pool.fork(a)
        assert all(pool.refcount(p) == 2 for p in a)
        assert pool.free(a) == []  # refs drop to 1: nothing released
        assert sorted(pool.free(a)) == sorted(a)
        pool.check()
        assert pool.pages_in_use == 1  # only the trash page

    def test_double_free_raises(self):
        pool = PagePool(8, 4)
        a = pool.alloc(2)
        pool.free(a)
        with pytest.raises(ValueError, match="double free"):
            pool.free(a)
        pool.check()

    def test_trash_page_is_pinned(self):
        pool = PagePool(8, 4)
        assert TRASH_PAGE not in pool.alloc(pool.capacity)
        with pytest.raises(ValueError):
            pool.free([TRASH_PAGE])
        with pytest.raises(ValueError):
            pool.fork([TRASH_PAGE])

    def test_exhaustion_is_atomic(self):
        pool = PagePool(8, 4)
        pool.alloc(5)
        before = pool.pages_free
        with pytest.raises(MemoryError):
            pool.alloc(3)  # only 2 free
        assert pool.pages_free == before  # nothing leaked
        pool.check()

    @staticmethod
    def _run_ops(ops, num_pages=16):
        """Interpret an (op, idx) stream against the pool, checking the
        accounting invariant after every operation."""
        pool = PagePool(num_pages, 4)
        held = []  # page lists this "scheduler" owns
        for kind, idx in ops:
            if kind == 0:
                try:
                    held.append(pool.alloc(1 + idx % 3))
                except MemoryError:
                    pass
            elif kind == 1 and held:
                pages = held[idx % len(held)]
                pool.fork(pages)
                held.append(list(pages))
            elif kind == 2 and held:
                pool.free(held.pop(idx % len(held)))
            pool.check()
            assert pool.pages_in_use + pool.pages_free == pool.num_pages
        for pages in held:
            pool.free(pages)
        pool.check()
        assert pool.pages_in_use == 1  # everything returned except trash

    @pytest.mark.parametrize("seed", range(10))
    def test_random_ops_keep_invariant(self, seed):
        """No sequence of alloc/fork/free can double-free or leak."""
        rng = np.random.default_rng(seed)
        ops = [(int(rng.integers(0, 3)), int(rng.integers(0, 64)))
               for _ in range(60)]
        self._run_ops(ops)

    @given(st.lists(st.tuples(st.integers(0, 2), st.integers(0, 63)),
                    max_size=80))
    @settings(max_examples=40, deadline=None)
    def test_random_ops_keep_invariant_hyp(self, ops):
        self._run_ops(ops)


# --------------------------------------------------------------------------
# PrefixTree properties
# --------------------------------------------------------------------------


class TestPrefixTree:
    def test_fork_then_free_leaves_shared_pages_live(self):
        """A slot retiring must not kill pages the tree (or another
        slot) still references."""
        pool = PagePool(32, 4)
        tree = PrefixTree(pool)
        toks = _tokens(16, seed=1)  # 4 full blocks
        pages = pool.alloc(4)
        tree.insert(toks, pages)
        pool.free(pages)  # first slot retires; tree refs keep them live
        m, n = tree.match(toks)
        assert n == 16 and len(m) == 4
        pool.fork(m)  # second slot shares the chain
        assert pool.free(m) == []  # ...and retires: tree still holds all
        m2, n2 = tree.match(toks)
        assert n2 == 16 and m2 == m
        pool.check()

    def test_refcounts_match_tree_reachability(self):
        """With no slots holding pages, every cached page's refcount is
        exactly the tree's one ref, and nothing else is in use."""
        pool = PagePool(64, 4)
        tree = PrefixTree(pool)
        rng = np.random.default_rng(2)
        base = _tokens(24, seed=3)  # 6 blocks
        for i in range(6):
            cut = 4 * int(rng.integers(1, 7))
            toks = np.concatenate([base[:cut], _tokens(8, seed=10 + i)])
            shared, skip = tree.match(toks, max_tokens=(len(toks) // 4) * 4)
            if shared:
                pool.fork(shared)
            n_pages = len(toks) // 4
            fresh = pool.alloc(n_pages - len(shared))
            tree.insert(toks[:n_pages * 4], list(shared) + fresh)
            pool.free(list(shared) + fresh)  # the slot retires at once
            pool.check()
        assert pool.pages_in_use == 1 + tree.cached_pages
        # the tree holds exactly one ref per cached page — reachability
        # equals refcount with no slot forks outstanding
        for nid in getattr(tree, "_nodes", {}):
            assert pool.refcount(tree._nodes[nid].page) == 1
        freed = tree.clear()
        pool.check()
        assert pool.pages_in_use == 1 and freed > 0

    def test_match_respects_token_cap(self):
        pool = PagePool(16, 4)
        tree = PrefixTree(pool)
        toks = _tokens(16, seed=4)
        tree.insert(toks, pool.alloc(4))
        m, n = tree.match(toks, max_tokens=8)
        assert n == 8 and len(m) == 2

    def test_reclaim_spares_forked_pages(self):
        """LRU reclaim frees tree-only chains; pages a live slot forked
        survive (refcount > 1)."""
        pool = PagePool(16, 4)
        tree = PrefixTree(pool)
        cold = _tokens(8, seed=5)
        hot = _tokens(8, seed=6)
        cold_pages = pool.alloc(2)
        tree.insert(cold, cold_pages)
        pool.free(cold_pages)  # slot retires: cold chain is tree-only
        hot_pages = pool.alloc(2)
        tree.insert(hot, hot_pages)  # this slot stays live (keeps refs)
        freed = tree.reclaim(4)
        assert freed == 2  # only the cold chain was evictable
        assert all(pool.refcount(p) >= 1 for p in hot_pages)
        m, n = tree.match(hot)
        assert n == 8  # hot chain survived
        pool.check()

    def test_build_row_table_pads_with_trash(self):
        row = build_row_table([3, 7], 4)
        assert row.dtype == np.int32
        assert list(row) == [3, 7, TRASH_PAGE, TRASH_PAGE]
        assert pages_for(17, 16) == 2 and pages_for(16, 16) == 1

    @given(st.lists(st.integers(0, 3), min_size=1, max_size=12),
           st.integers(1, 5))
    @settings(max_examples=30, deadline=None)
    def test_shared_prefix_reuse_hyp(self, symbols, reps):
        """Inserting the same token stream repeatedly never allocates
        new pages past the first insert, and refcounts stay consistent."""
        pool = PagePool(64, 2)
        tree = PrefixTree(pool)
        toks = np.asarray(symbols, np.int32)
        nfull = (len(toks) // 2) * 2
        if nfull == 0:
            return
        for _ in range(reps):
            shared, skip = tree.match(toks, max_tokens=nfull)
            if shared:
                pool.fork(shared)
            fresh = pool.alloc(nfull // 2 - len(shared))
            tree.insert(toks[:nfull], list(shared) + fresh)
            pool.free(list(shared) + fresh)
            pool.check()
        assert pool.pages_in_use == 1 + tree.cached_pages
        assert tree.cached_pages == nfull // 2


# --------------------------------------------------------------------------
# paged ≡ contiguous fidelity (bitwise)
# --------------------------------------------------------------------------


@pytest.fixture(scope="module", params=["forge-125m", "qwen2.5-14b"])
def fid_setup(request):
    """Dense MHA smoke + a GQA smoke (n_kv_heads < n_heads)."""
    cfg = get_config(request.param, smoke=True)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0), cfg)
    return cfg, model, params


def _identity_paged_cache(model, cfg, B, max_len, ps):
    """Paged cache whose tables map slot rows to disjoint page runs —
    the contiguous layout expressed through the indirection."""
    MP = max_len // ps
    cache = model.init_paged_cache(
        cfg, B, max_len, num_pages=1 + B * MP, page_size=ps
    )
    pt = np.zeros((B, MP), np.int32)
    for b in range(B):
        pt[b] = 1 + b * MP + np.arange(MP)
    cache["page_table"] = jnp.asarray(pt)
    return cache


class TestPagedDecodeFidelity:
    B, T, MAX_LEN, PS = 2, 9, 32, 8

    def test_decode_bitwise(self, fid_setup):
        """Token-at-a-time decode: the paged path must be bit-identical
        to the contiguous cache, dense and GQA alike."""
        cfg, model, params = fid_setup
        B, T, max_len = self.B, self.T, self.MAX_LEN
        cache = model.init_cache(cfg, B, max_len)
        pcache = _identity_paged_cache(model, cfg, B, max_len, self.PS)
        toks = np.stack([_tokens(T, seed=7, vocab=cfg.vocab),
                         _tokens(T, seed=8, vocab=cfg.vocab)])
        mask = jnp.ones((B,), bool)
        for t in range(T):
            tok = jnp.asarray(toks[:, t:t + 1])
            pos = jnp.full((B,), t, jnp.int32)
            la, cache = model.decode_step(params, cache, tok, pos, cfg,
                                          slot_mask=mask)
            lb, pcache = model.paged_decode_step(params, pcache, tok, pos,
                                                 cfg, slot_mask=mask)
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))

    def test_prefill_then_decode_bitwise(self, fid_setup):
        """Whole-prompt paged prefill ≡ contiguous prefill, and the
        caches they leave behind decode identically."""
        cfg, model, params = fid_setup
        B, P, max_len = self.B, 12, self.MAX_LEN
        cache = model.init_cache(cfg, B, max_len)
        pcache = _identity_paged_cache(model, cfg, B, max_len, self.PS)
        toks = jnp.asarray(np.stack([
            _tokens(P, seed=9, vocab=cfg.vocab),
            _tokens(P, seed=10, vocab=cfg.vocab),
        ]))
        mask = jnp.ones((B,), bool)
        la, cache = model.prefill_step(params, cache, toks, 0, cfg,
                                       slot_mask=mask)
        lb, pcache = model.paged_prefill_step(params, pcache, toks, 0, cfg,
                                             slot_mask=mask)
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
        tok = jnp.argmax(la[:, -1, :], axis=-1).astype(jnp.int32)[:, None]
        for t in range(3):
            pos = jnp.full((B,), P + t, jnp.int32)
            la, cache = model.decode_step(params, cache, tok, pos, cfg,
                                          slot_mask=mask)
            lb, pcache = model.paged_decode_step(params, pcache, tok, pos,
                                                 cfg, slot_mask=mask)
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
            tok = jnp.argmax(la[:, -1, :], axis=-1).astype(jnp.int32)[:, None]

    def test_interpret_kernel_decode_matches_gather(self, fid_setup):
        """kv_kernel="interpret" runs the Pallas paged kernel, interpreted,
        inside the model: same logits as the gather path to f32 rounding
        (the kernel's online softmax sums in another order)."""
        cfg, model, params = fid_setup
        kcfg = cfg.with_(kv_kernel="interpret")
        B, T, max_len = self.B, 4, self.MAX_LEN
        ca = _identity_paged_cache(model, cfg, B, max_len, self.PS)
        cb = _identity_paged_cache(model, kcfg, B, max_len, self.PS)
        toks = np.stack([_tokens(T, seed=12, vocab=cfg.vocab),
                         _tokens(T, seed=13, vocab=cfg.vocab)])
        mask = jnp.ones((B,), bool)
        for t in range(T):
            tok = jnp.asarray(toks[:, t:t + 1])
            pos = jnp.full((B,), t, jnp.int32)
            la, ca = model.paged_decode_step(params, ca, tok, pos, cfg,
                                             slot_mask=mask)
            lb, cb = model.paged_decode_step(params, cb, tok, pos, kcfg,
                                             slot_mask=mask)
            np.testing.assert_allclose(
                np.asarray(la, np.float32), np.asarray(lb, np.float32),
                atol=5e-2, rtol=5e-2,
            )

    def test_pallas_kernel_never_interprets_on_its_own(self, fid_setup):
        """kv_kernel="pallas" is the compiled TPU kernel: off the chip it
        raises instead of quietly running interpreted."""
        cfg, model, params = fid_setup
        kcfg = cfg.with_(kv_kernel="pallas")
        cache = _identity_paged_cache(model, kcfg, self.B, self.MAX_LEN,
                                      self.PS)
        tok = jnp.zeros((self.B, 1), jnp.int32)
        pos = jnp.zeros((self.B,), jnp.int32)
        with pytest.raises(ValueError, match="interpret"):
            model.paged_decode_step(params, cache, tok, pos, kcfg,
                                    slot_mask=jnp.ones((self.B,), bool))

    def test_masked_rows_leave_pages_untouched(self, fid_setup):
        """slot_mask=False rows write nothing: their writes land on the
        trash page, so every real page survives bitwise."""
        cfg, model, params = fid_setup
        B, max_len = self.B, self.MAX_LEN
        pcache = _identity_paged_cache(model, cfg, B, max_len, self.PS)
        mask = jnp.asarray([True, False])
        tok = jnp.asarray([[3], [5]], jnp.int32)
        pos = jnp.asarray([0, 0], jnp.int32)
        _, out = model.paged_decode_step(params, pcache, tok, pos, cfg,
                                         slot_mask=mask)
        MP = max_len // self.PS
        row1_pages = np.asarray(pcache["page_table"])[1]
        for name in ("k_pages", "v_pages"):
            new = np.asarray(out[name])
            assert np.all(new[:, row1_pages] == 0.0), \
                "masked row wrote into its own pages"

    def test_window_attention_bitwise(self):
        """Sliding-window decode through the paged cache matches the
        contiguous rotating mask path bitwise (attention-layer level)."""
        H, KVH, D, max_len, ps, window = 4, 2, 8, 32, 8, 8
        B, d_model = 2, 32
        key = jax.random.PRNGKey(1)
        p = attn_init(key, d_model, H, KVH, D, dtype=jnp.float32)
        cache = make_cache(B, KVH, max_len, D, dtype=jnp.float32)
        MP = max_len // ps
        pt = np.zeros((B, MP), np.int32)
        for b in range(B):
            pt[b] = 1 + b * MP + np.arange(MP)
        pt_dev = jnp.asarray(pt)
        # a one-layer token-major store: (layers, pages, ps, KVH * D)
        store = {
            "k_pages": jnp.zeros((1, 1 + B * MP, ps, KVH * D), jnp.float32),
            "v_pages": jnp.zeros((1, 1 + B * MP, ps, KVH * D), jnp.float32),
        }
        rng = np.random.default_rng(11)
        mask = jnp.ones((B,), bool)
        for t in range(2 * window):  # run PAST the window edge
            x = jnp.asarray(rng.standard_normal((B, 1, d_model)),
                            jnp.float32)
            pos = jnp.full((B,), t, jnp.int32)
            oa, cache = attention(x, p, n_heads=H, n_kv_heads=KVH,
                                  window=window, cache=cache, cache_pos=pos)
            # the returned store has no table — the table rides separately
            # (steps.py passes it per dispatch), so re-attach each step
            ob, store = attention(x, p, n_heads=H, n_kv_heads=KVH,
                                  window=window,
                                  cache={**store, "page_table": pt_dev},
                                  cache_pos=pos, kv_layer=0,
                                  write_mask=mask)
            np.testing.assert_array_equal(np.asarray(oa), np.asarray(ob))


class TestTokenMajorStore:
    """The stacked store (layers, pages, page_size, KVH * D) rides in the
    layer loop's carry: a step writes whole token rows into its own
    layer's pages and leaves every other row of the store bitwise as it
    was."""

    B, MAX_LEN, PS = 2, 32, 8

    def _random_store(self, model, cfg, seed):
        cache = _identity_paged_cache(model, cfg, self.B, self.MAX_LEN,
                                      self.PS)
        rng = np.random.default_rng(seed)
        for name in ("k_pages", "v_pages"):
            cache[name] = jnp.asarray(
                rng.standard_normal(cache[name].shape), cache[name].dtype)
        return cache

    def test_init_paged_cache_is_token_major(self, fid_setup):
        cfg, model, _ = fid_setup
        cache = model.init_paged_cache(cfg, self.B, self.MAX_LEN,
                                       num_pages=9, page_size=self.PS)
        row = cfg.n_kv_heads * cfg.head_dim_
        for name in ("k_pages", "v_pages"):
            assert cache[name].shape == (cfg.n_layers, 9, self.PS, row)
            assert cache[name].dtype == jnp.dtype(cfg.dtype)
            assert not np.any(np.asarray(cache[name]))
        assert cache["page_table"].shape == (self.B, self.MAX_LEN // self.PS)

    def test_decode_writes_only_live_rows_in_each_layer(self, fid_setup):
        """Row 0 live at position 5, row 1 masked: in every layer exactly
        row 0's slot (its page for position 5, offset 5 % ps) changes;
        every other row of every real page keeps its bits."""
        cfg, model, params = fid_setup
        cache = self._random_store(model, cfg, seed=21)
        pt = np.asarray(cache["page_table"])
        tok = jnp.asarray([[3], [5]], jnp.int32)
        pos = jnp.asarray([5, 9], jnp.int32)
        _, out = model.paged_decode_step(params, cache, tok, pos, cfg,
                                         slot_mask=jnp.asarray([True, False]))
        want = np.zeros(cache["k_pages"].shape[:3], bool)
        want[:, pt[0, 5 // self.PS], 5 % self.PS] = True
        for name in ("k_pages", "v_pages"):
            changed = np.any(np.asarray(out[name]) != np.asarray(cache[name]),
                             axis=-1)
            # page 0 is the trash page the masked row's write went to
            np.testing.assert_array_equal(changed[:, 1:], want[:, 1:],
                                          err_msg=name)

    def test_prefill_chunk_writes_only_its_positions(self, fid_setup):
        """A prefill chunk written as whole pages, at offsets inside a page
        (row 0 from position 3, row 1 from 10; row 2 masked): in every
        layer exactly each live row's positions [pos, pos + S) change, and
        the rest of the pages the chunk touches keeps its bits."""
        cfg, model, params = fid_setup
        B, S = 3, 6
        cache = model.init_paged_cache(cfg, B, self.MAX_LEN,
                                       num_pages=1 + B * 4, page_size=self.PS)
        rng = np.random.default_rng(25)
        pt = np.stack([1 + b * 4 + rng.permutation(4) for b in range(B)])
        cache["page_table"] = jnp.asarray(pt, jnp.int32)
        for name in ("k_pages", "v_pages"):
            cache[name] = jnp.asarray(
                rng.standard_normal(cache[name].shape), cache[name].dtype)
        toks = jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32)
        pos = np.asarray([3, 10, 0], np.int32)
        _, out = model.paged_prefill_step(
            params, cache, toks, jnp.asarray(pos), cfg,
            slot_mask=jnp.asarray([True, True, False]))
        want = np.zeros(cache["k_pages"].shape[:3], bool)
        for b in (0, 1):
            for t in range(pos[b], pos[b] + S):
                want[:, pt[b, t // self.PS], t % self.PS] = True
        for name in ("k_pages", "v_pages"):
            changed = np.any(np.asarray(out[name]) != np.asarray(cache[name]),
                             axis=-1)
            np.testing.assert_array_equal(changed[:, 1:], want[:, 1:],
                                          err_msg=name)

    @pytest.mark.parametrize("layer", [0, 1, 2])
    def test_layer_write_stays_in_its_layer(self, layer):
        """Attention against layer ``layer`` of a three-layer store writes
        and reads that layer alone: the other layers keep their bits, and
        output and written layer equal a one-layer store holding it."""
        H, KVH, D, max_len, ps, B, d_model = 4, 2, 8, 32, 8, 2, 32
        MP = max_len // ps
        p = attn_init(jax.random.PRNGKey(3), d_model, H, KVH, D,
                      dtype=jnp.float32)
        rng = np.random.default_rng(4)
        pt = np.zeros((B, MP), np.int32)
        for b in range(B):
            pt[b] = 1 + b * MP + rng.permutation(MP)
        shape = (3, 1 + B * MP, ps, KVH * D)
        store = {n: jnp.asarray(rng.standard_normal(shape), jnp.float32)
                 for n in ("k_pages", "v_pages")}
        x = jnp.asarray(rng.standard_normal((B, 1, d_model)), jnp.float32)
        pos = jnp.asarray([6, 17], jnp.int32)
        mask = jnp.ones((B,), bool)
        kw = dict(n_heads=H, n_kv_heads=KVH, cache_pos=pos, write_mask=mask)
        out, new = attention(x, p, cache={**store, "page_table": jnp.asarray(pt)},
                             kv_layer=layer, **kw)
        one = {n: store[n][layer:layer + 1] for n in store}
        out1, new1 = attention(x, p, cache={**one, "page_table": jnp.asarray(pt)},
                               kv_layer=0, **kw)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(out1))
        for n in store:
            got, was = np.asarray(new[n]), np.asarray(store[n])
            np.testing.assert_array_equal(got[layer], np.asarray(new1[n])[0])
            others = [i for i in range(3) if i != layer]
            np.testing.assert_array_equal(got[others], was[others])
            assert np.any(got[layer] != was[layer])

    def test_unrolled_layers_decode_bitwise(self, fid_setup):
        """``scan_layers=False`` threads the store through a Python layer
        loop with the same body: still bitwise the contiguous cache."""
        cfg, model, params = fid_setup
        cfg = cfg.with_(scan_layers=False)
        params = dict(params, blocks=[
            jax.tree_util.tree_map(lambda a, i=i: a[i], params["blocks"])
            for i in range(cfg.n_layers)
        ])
        cache = model.init_cache(cfg, self.B, self.MAX_LEN)
        pcache = _identity_paged_cache(model, cfg, self.B, self.MAX_LEN,
                                       self.PS)
        toks = np.stack([_tokens(5, seed=23, vocab=cfg.vocab),
                         _tokens(5, seed=24, vocab=cfg.vocab)])
        mask = jnp.ones((self.B,), bool)
        for t in range(5):
            tok = jnp.asarray(toks[:, t:t + 1])
            pos = jnp.full((self.B,), t, jnp.int32)
            la, cache = model.decode_step(params, cache, tok, pos, cfg,
                                          slot_mask=mask)
            lb, pcache = model.paged_decode_step(params, pcache, tok, pos,
                                                 cfg, slot_mask=mask)
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


class TestPagedSchedulerFidelity:
    """End-to-end: the paged SlotScheduler emits bitwise the contiguous
    scheduler's tokens — through rung resizes, mid-generation swap-ins,
    and prefix-tree admission hits."""

    MAX_LEN, PS = 32, 8

    def _requests(self, vocab):
        shared = _tokens(16, seed=20, vocab=vocab)  # 2 shared pages
        reqs = []
        for i in range(8):
            if i % 3 == 0:  # shared-prefix group → prefix-tree hits
                p = np.concatenate([shared,
                                    _tokens(4, seed=30 + i, vocab=vocab)])
            else:
                p = _tokens(3 + 2 * (i % 5), seed=40 + i, vocab=vocab)
            reqs.append(Request(rid=i, prompt=p,
                                max_new=2 + (3 * i) % 5, arrival=i // 3))
        return reqs

    def _run(self, cfg, params, paged, **kw):
        srv = BatchedServer(cfg, params, max_len=self.MAX_LEN, mode="forge",
                            backend="interpret",
                            seq_bucket_policy="ladder:8,16,32",
                            paged=paged, kv_page_size=self.PS, **kw)
        sched = SlotScheduler(srv, max_slots=4)
        sched.warmup(prompt_lens=[4, 8, 16, 24])
        res = sched.run(self._requests(cfg.vocab))
        if paged:
            srv.page_pool.check()
            # every slot freed its pages: only the trash page and the
            # prefix tree's cached chains remain referenced
            assert srv.page_pool.pages_in_use == \
                1 + srv.prefix_tree.cached_pages
        return res

    def test_swap_in_and_prefix_hits_bitwise(self, fid_setup):
        cfg, _, params = fid_setup
        ra = self._run(cfg, params, paged=False)
        rb = self._run(cfg, params, paged=True)
        assert rb["swaps"] >= 1, "workload must exercise swap-in"
        assert rb["prefix_hits"] >= 1, "workload must hit the prefix tree"
        assert rb["tokens_reused"] >= 16
        assert set(ra["results"]) == set(rb["results"])
        for rid in ra["results"]:
            np.testing.assert_array_equal(
                ra["results"][rid]["tokens"], rb["results"][rid]["tokens"],
                err_msg=f"rid {rid} diverged between paged and contiguous",
            )

    def test_pool_exhaustion_defers_and_completes(self, fid_setup):
        """A pool too small for all concurrent admissions bounces the
        overflow back to the queue; every request still completes with
        the same tokens."""
        cfg, _, params = fid_setup
        ra = self._run(cfg, params, paged=False)
        # capacity 5: the first admission wave wants 7 pages, so at
        # least one request bounces and re-admits after a retirement
        rb = self._run(cfg, params, paged=True, kv_pages=6)
        assert rb["deferrals"] >= 1, "pool must have been exhausted"
        assert set(ra["results"]) == set(rb["results"])
        for rid in ra["results"]:
            np.testing.assert_array_equal(
                ra["results"][rid]["tokens"], rb["results"][rid]["tokens"])


# --------------------------------------------------------------------------
# Pallas paged-attention kernel vs jnp reference (interpret mode)
# --------------------------------------------------------------------------


class TestPagedAttentionKernel:
    def _case(self, seed, B, H, KVH, D, ps, MP, window, dtype):
        rng = np.random.default_rng(seed)
        NP = 1 + B * MP
        q = jnp.asarray(rng.standard_normal((B, H, D)), dtype)
        k = jnp.asarray(rng.standard_normal((NP, KVH, ps, D)), dtype)
        v = jnp.asarray(rng.standard_normal((NP, KVH, ps, D)), dtype)
        pt = np.zeros((B, MP), np.int32)
        for b in range(B):
            pt[b] = 1 + b * MP + rng.permutation(MP)  # non-contiguous!
        pos = rng.integers(0, MP * ps, (B,)).astype(np.int32)
        pt, pos = jnp.asarray(pt), jnp.asarray(pos)
        out = paged_attention(q, k, v, pt, pos, window=window,
                              interpret=True)
        ref = paged_sdpa_ref(q, k, v, pt, pos, window=window)
        assert out.dtype == q.dtype
        tol = 2e-5 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=tol, rtol=tol,
        )

    @pytest.mark.parametrize(
        "seed,B,H,KVH,D,ps,MP,window",
        [
            (0, 2, 4, 4, 8, 8, 4, None),   # MHA
            (1, 2, 4, 2, 8, 8, 4, None),   # GQA
            (2, 3, 6, 2, 16, 4, 6, None),  # deeper GQA, small pages
            (3, 2, 4, 2, 8, 8, 4, 8),      # sliding window
            (4, 1, 8, 8, 32, 16, 2, 16),   # wide head, window
        ],
    )
    def test_kernel_matches_reference(self, seed, B, H, KVH, D, ps, MP,
                                      window):
        self._case(seed, B, H, KVH, D, ps, MP, window, jnp.float32)

    def test_kernel_bf16(self):
        self._case(5, 2, 4, 2, 8, 8, 4, None, jnp.bfloat16)

    def test_gather_pages_reconstructs_contiguous_layout(self):
        rng = np.random.default_rng(6)
        B, KVH, D, ps, MP = 2, 2, 4, 4, 3
        NP = 1 + B * MP
        pages = jnp.asarray(rng.standard_normal((NP, KVH, ps, D)),
                            jnp.float32)
        pt = np.zeros((B, MP), np.int32)
        for b in range(B):
            pt[b] = 1 + b * MP + np.arange(MP)
        view = np.asarray(gather_pages(pages, jnp.asarray(pt)))
        assert view.shape == (B, KVH, MP * ps, D)
        flat = np.asarray(pages)
        for b in range(B):
            # token t of row b sits in page pt[b, t // ps] at t % ps
            for t in range(MP * ps):
                np.testing.assert_array_equal(
                    view[b, :, t], flat[pt[b, t // ps], :, t % ps]
                )

    def test_fully_masked_row_yields_zeros_not_nan(self):
        """pos = -1 keeps every key masked; the kernel's l==0 guard must
        return zeros instead of 0/0 NaNs."""
        B, H, KVH, D, ps, MP = 1, 2, 2, 8, 4, 2
        q = jnp.ones((B, H, D), jnp.float32)
        k = jnp.ones((1 + MP, KVH, ps, D), jnp.float32)
        v = jnp.ones((1 + MP, KVH, ps, D), jnp.float32)
        pt = jnp.asarray([[1, 2]], jnp.int32)
        pos = jnp.asarray([-1], jnp.int32)
        out = paged_attention(q, k, v, pt, pos, interpret=True)
        assert np.all(np.isfinite(np.asarray(out)))
