"""Property tests on the core engine's invariants (hypothesis)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # env without hypothesis: deterministic fallback
    from _hypo import given, settings, st

from repro.configs import get_smoke_config
from repro.core import bucketing
from repro.core.flash_decode import flash_decode_ref
from repro.kernels.flash_attention import flash_attention
from repro.models import attention as attn_mod
from repro.models import lm
from repro.models.attention import masked_attention


class TestBucketingProperties:
    @given(n=st.integers(1, 20), aggr_kib=st.sampled_from([0, 1, 16, 1024]),
           seed=st.integers(0, 5))
    @settings(max_examples=30, deadline=None)
    def test_bucketed_apply_identity_roundtrip(self, n, aggr_kib, seed):
        """bucketed_apply with the identity fn is the identity, for any
        leaf-set and aggregation threshold."""
        rng = np.random.default_rng(seed)
        tree = {f"w{i}": jnp.asarray(
            rng.standard_normal(tuple(rng.integers(1, 24, rng.integers(1, 3))))
            .astype(np.float32)) for i in range(n)}
        out = bucketing.bucketed_apply(tree, lambda flat, b: flat,
                                       aggr_bytes=aggr_kib << 10)
        for k in tree:
            np.testing.assert_array_equal(np.asarray(tree[k]),
                                          np.asarray(out[k]))

    @given(n=st.integers(1, 30), aggr=st.sampled_from([0, 256, 4096, 1 << 20]))
    @settings(max_examples=40, deadline=None)
    def test_plan_partitions_leaves_exactly_once(self, n, aggr):
        leaves = [jnp.zeros((i % 7 + 1, 3)) for i in range(n)]
        plan = bucketing.make_plan(leaves, aggr)
        seen = sorted(i for b in plan.buckets for i in b.leaf_ids)
        assert seen == list(range(n))
        # buckets respect the threshold unless a single leaf exceeds it
        for b in plan.buckets:
            if len(b.leaf_ids) > 1 and aggr > 0:
                assert b.nbytes <= aggr

    @given(aggr=st.sampled_from([0, 100, 10_000, 1 << 30]))
    @settings(max_examples=10, deadline=None)
    def test_more_aggregation_fewer_buckets(self, aggr):
        leaves = [jnp.zeros((16,)) for _ in range(12)]
        base = bucketing.make_plan(leaves, 0).n_buckets
        assert bucketing.make_plan(leaves, aggr).n_buckets <= base


def _blocks_traced(f, *args):
    """(scored, grid) key blocks that tracing ``jit(f)(*args)`` adds to
    ``attention.block_stats()``."""
    before = attn_mod.block_stats()
    jax.jit(f).lower(*args)
    after = attn_mod.block_stats()
    return (after["scored"] - before["scored"],
            after["grid"] - before["grid"])


class TestAttentionConsistency:
    """The three attention implementations agree: model path (chunked
    masked_attention), Pallas kernel, and the decode oracle."""

    @given(seed=st.integers(0, 4), window=st.sampled_from([0, 32]),
           kv=st.sampled_from([1, 2, 4]))
    @settings(max_examples=10, deadline=None)
    def test_model_path_vs_pallas_kernel(self, seed, window, kv):
        b, h, s, d = 1, 4, 128, 32
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        q = jax.random.normal(ks[0], (b, s, h, d))
        k = jax.random.normal(ks[1], (b, s, kv, d))
        v = jax.random.normal(ks[2], (b, s, kv, d))
        model = masked_attention(q, k, v, q_pos=jnp.arange(s),
                                 k_pos=jnp.arange(s), window=window,
                                 scale=d ** -0.5, q_chunk=64)
        kern = flash_attention(q.transpose(0, 2, 1, 3),
                               k.transpose(0, 2, 1, 3),
                               v.transpose(0, 2, 1, 3),
                               causal=True, window=window,
                               block_q=32, block_k=32, interpret=True)
        np.testing.assert_allclose(np.asarray(model),
                                   np.asarray(kern.transpose(0, 2, 1, 3)),
                                   rtol=2e-5, atol=2e-5)

    @given(seed=st.integers(0, 4), pos=st.sampled_from([0, 17, 63]))
    @settings(max_examples=10, deadline=None)
    def test_model_decode_vs_flash_decode_oracle(self, seed, pos):
        b, h, kv, s, d = 2, 4, 2, 64, 16
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        q = jax.random.normal(ks[0], (b, 1, h, d))
        k = jax.random.normal(ks[1], (b, s, kv, d))
        v = jax.random.normal(ks[2], (b, s, kv, d))
        q_pos = jnp.full((b, 1), pos)
        model = masked_attention(q, k, v, q_pos=q_pos, k_pos=jnp.arange(s),
                                 scale=d ** -0.5)
        oracle = flash_decode_ref(q[:, 0], k, v, pos=jnp.int32(pos),
                                  scale=d ** -0.5)
        np.testing.assert_allclose(np.asarray(model[:, 0]),
                                   np.asarray(oracle), rtol=2e-5, atol=2e-5)

    # (query heads per kv head, dk, dv, window, softcap, positions)
    @pytest.mark.parametrize("group,dk,dv,window,cap,pos", [
        (1, 32, 32, 0, None, "1d"),
        (2, 32, 32, 32, None, "1d"),
        (4, 32, 32, 0, 30.0, "1d"),
        (2, 48, 32, 0, None, "batch"),
        (4, 48, 32, 32, 30.0, "batch"),
        (1, 48, 32, 32, 30.0, "1d"),
    ])
    def test_chunked_gradient_matches_one_block(self, group, dk, dv, window,
                                                cap, pos):
        """The chunked path's recompute backward against autodiff of one
        unchunked block, in q, k and v."""
        b, s, kv, qc = 2, 128, 2, 32
        ks = jax.random.split(jax.random.PRNGKey(group + dk + window), 4)
        q = jax.random.normal(ks[0], (b, s, kv * group, dk))
        k = jax.random.normal(ks[1], (b, s, kv, dk))
        v = jax.random.normal(ks[2], (b, s, kv, dv))
        ct = jax.random.normal(ks[3], (b, s, kv * group, dv))
        k_pos = jnp.arange(s)
        # per-batch rows: the second sequence starts 16 positions later
        q_pos = k_pos if pos == "1d" else k_pos + jnp.array([[0], [16]])
        scale = dk ** -0.5

        def chunked(q, k, v):
            return jnp.sum(ct * masked_attention(
                q, k, v, q_pos=q_pos, k_pos=k_pos, window=window,
                attn_softcap=cap, scale=scale, q_chunk=qc))

        def block(q, k, v):
            return jnp.sum(ct * attn_mod._attn_block(
                q, k, v, q_pos, k_pos, window, cap, scale, q.dtype))

        got = jax.jit(jax.grad(chunked, (0, 1, 2)))(q, k, v)
        want = jax.jit(jax.grad(block, (0, 1, 2)))(q, k, v)
        for g, w in zip(got, want):
            assert g.dtype == w.dtype
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=2e-5, atol=2e-5)

    # (query heads per kv head, dk, dv, window, softcap, query chunks)
    @pytest.mark.parametrize("group,dk,dv,window,cap,nc", [
        (1, 32, 32, 0, None, 2),
        (2, 48, 32, 32, None, 4),
        (4, 48, 32, 0, 30.0, 8),
        (1, 48, 32, 32, 30.0, 4),
        (2, 32, 32, 0, 30.0, 8),
        (4, 48, 32, 32, None, 2),
        (2, 48, 32, 32, 30.0, 8),
    ])
    def test_index_positions_match_one_block(self, group, dk, dv, window,
                                             cap, nc):
        """Positions None (the token indices): the chunked path, which
        scores only each chunk's causal key prefix, against one
        unchunked block with explicit aranges, in the output and in the
        gradient in q, k and v."""
        b, s, kv = 2, 128, 2
        ks = jax.random.split(jax.random.PRNGKey(group + dk + nc), 4)
        q = jax.random.normal(ks[0], (b, s, kv * group, dk))
        k = jax.random.normal(ks[1], (b, s, kv, dk))
        v = jax.random.normal(ks[2], (b, s, kv, dv))
        ct = jax.random.normal(ks[3], (b, s, kv * group, dv))
        pos = jnp.arange(s)
        scale = dk ** -0.5

        def chunked(q, k, v):
            return masked_attention(q, k, v, window=window,
                                    attn_softcap=cap, scale=scale,
                                    q_chunk=s // nc)

        def block(q, k, v):
            return attn_mod._attn_block(q, k, v, pos, pos, window, cap,
                                        scale, q.dtype)

        np.testing.assert_allclose(np.asarray(jax.jit(chunked)(q, k, v)),
                                   np.asarray(jax.jit(block)(q, k, v)),
                                   rtol=2e-5, atol=2e-5)
        got, want = (jax.jit(jax.grad(lambda *a: jnp.sum(ct * f(*a)),
                                      (0, 1, 2)))(q, k, v)
                     for f in (chunked, block))
        for g, w in zip(got, want):
            assert g.dtype == w.dtype
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("s,qc", [(128, 64), (256, 64), (4096, 512)])
    def test_block_counts(self, s, qc):
        """Each traced chunked pass (the primal; under a gradient its
        forward and its backward) scores nc(nc+1)/2 of the nc^2 key
        blocks with index positions (36 of 64 for 4,096 tokens in chunks
        of 512), and every block with position arrays."""
        b, h, d, nc = 1, 2, 8, s // qc
        x = jax.ShapeDtypeStruct((b, s, h, d), jnp.float32)
        pos = jnp.arange(s)

        def blocks(**kw):
            def fwd(q, k, v):
                return masked_attention(q, k, v, scale=d ** -0.5,
                                        q_chunk=qc, **kw)

            grad = jax.grad(lambda *a: jnp.sum(fwd(*a)), (0, 1, 2))
            return [_blocks_traced(f, x, x, x) for f in (fwd, grad)]

        causal = nc * (nc + 1) // 2
        assert blocks() == [(causal, nc * nc), (2 * causal, 2 * nc * nc)]
        assert blocks(q_pos=pos, k_pos=pos) == [(nc * nc, nc * nc),
                                                (2 * nc * nc, 2 * nc * nc)]

    @pytest.mark.parametrize("arch_id", ["granite-moe-3b-a800m",
                                         "moonshot-v1-16b-a3b"])
    def test_model_block_counts(self, arch_id):
        """Through a small model: prefill, which writes a cache and so
        passes position arrays, skips no key block; the training loss's
        gradient scores the causal share of them."""
        s, qc = 32, 8
        nc = s // qc
        cfg = get_smoke_config(arch_id).replace(q_chunk=qc)
        params = jax.eval_shape(lambda: lm.init_params(
            cfg, jax.random.PRNGKey(0)))
        tok = jax.ShapeDtypeStruct((2, s), jnp.int32)
        bias = ({"route_bias": lm.init_route_state(cfg)["bias"]}
                if cfg.moe is not None and cfg.moe.biased else {})

        def prefill(p, t):
            return lm.prefill(cfg, p, {"tokens": t}, **bias)

        def grad(p, t):
            return jax.grad(lambda p: lm.loss_fn(
                cfg, p, {"tokens": t, "labels": t}, **bias))(p)

        for f, share in ((prefill, 1), (grad, (nc + 1) / (2 * nc))):
            scored, grid = _blocks_traced(f, params, tok)
            assert grid > 0
            assert scored == grid * share

    def test_chunked_gradient_keeps_no_score_stack(self):
        """The compiled gradient of the chunked path holds no buffer of
        every chunk's scores (n_chunks x q_chunk x Sk per head: autodiff of
        the scan stacks f32[8,1,4,1,128,1024] here), and only a
        differentiated trace takes the recompute backward."""
        b, s, h, d, qc = 1, 1024, 4, 32, 128
        x = jnp.ones((b, s, h, d))
        pos = jnp.arange(s)

        def fwd(q, k, v):
            return masked_attention(q, k, v, q_pos=pos, k_pos=pos,
                                    scale=d ** -0.5, q_chunk=qc)

        before = attn_mod.recompute_stats()["chunked_vjp"]
        jax.jit(fwd).lower(x, x, x).compile()
        assert attn_mod.recompute_stats()["chunked_vjp"] == before
        text = jax.jit(jax.grad(lambda q, k, v: jnp.sum(fwd(q, k, v)),
                                (0, 1, 2))).lower(x, x, x).compile().as_text()
        assert attn_mod.recompute_stats()["chunked_vjp"] >= before + 1
        largest = max(int(np.prod([int(n) for n in dims.split(",")]))
                      for dims in re.findall(r"\b(?:f32|bf16)\[([\d,]+)\]",
                                             text))
        assert largest < b * h * s * s, largest
