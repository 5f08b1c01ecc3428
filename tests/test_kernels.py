"""Per-kernel shape/dtype sweeps vs. the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels.embedding_bag import embedding_bag_pallas, embedding_bag_ref
from repro.kernels.flash_attention import flash_attention, gqa_ref
from repro.kernels.segment_reduce import (BlockedSegmentReducer,
                                          segment_max_ref, segment_min_ref,
                                          segment_sum_ref)


def _binned(rng, e, v, b):
    raw = rng.integers(0, v, e)
    order = np.argsort(raw // b, kind="stable")
    ids = raw[order]
    bp = np.zeros((v + b - 1) // b + 1, np.int64)
    np.add.at(bp, raw // b + 1, 1)
    return ids, np.cumsum(bp)


class TestSegmentReduce:
    @pytest.mark.parametrize("e,v,b,d", [
        (1000, 300, 64, 1), (4096, 512, 128, 8), (777, 100, 32, 5),
        (64, 512, 128, 1),   # sparser than segments
        (2048, 64, 64, 16),  # single block
    ])
    @pytest.mark.parametrize("kind", ["sum", "min", "max"])
    def test_matches_oracle(self, e, v, b, d, kind):
        rng = np.random.default_rng(e + v)
        ids, bp = _binned(rng, e, v, b)
        vals = rng.standard_normal((e, d)).astype(np.float32)
        x = jnp.asarray(vals if d > 1 else vals[:, 0])
        red = BlockedSegmentReducer(ids, bp, v, b, tile_e=256)
        got = np.asarray(red.reduce(x, kind))
        ref_fn = {"sum": segment_sum_ref, "min": segment_min_ref,
                  "max": segment_max_ref}[kind]
        ref = np.asarray(ref_fn(x, jnp.asarray(ids), v))
        np.testing.assert_allclose(got, ref, atol=1e-4)

    def test_int32_min(self):
        rng = np.random.default_rng(0)
        ids, bp = _binned(rng, 500, 200, 64)
        vals = jnp.asarray(rng.integers(0, 10**6, 500).astype(np.int32))
        red = BlockedSegmentReducer(ids, bp, 200, 64)
        got = np.asarray(red.min(vals))
        ref = np.asarray(segment_min_ref(vals, jnp.asarray(ids), 200))
        np.testing.assert_array_equal(got, ref)

    @pytest.mark.parametrize("kind", ["sum", "min", "max"])
    def test_masked_matches_filtered_oracle(self, kind):
        """masked() == reducing only the surviving edges: the predicate
        entry point used by both the push/owned and pull/CSC paths."""
        rng = np.random.default_rng(42)
        e, v, b = 800, 256, 64
        ids, bp = _binned(rng, e, v, b)
        vals = rng.standard_normal(e).astype(np.float32)
        mask = rng.random(e) < 0.6
        red = BlockedSegmentReducer(ids, bp, v, b)
        got = np.asarray(red.masked(jnp.asarray(vals), jnp.asarray(mask),
                                    kind))
        ident = float(BlockedSegmentReducer.identity(kind, np.float32))
        ref_fn = {"sum": segment_sum_ref, "min": segment_min_ref,
                  "max": segment_max_ref}[kind]
        ref = np.asarray(ref_fn(jnp.where(jnp.asarray(mask),
                                          jnp.asarray(vals), ident),
                                jnp.asarray(ids), v))
        np.testing.assert_allclose(got, ref, atol=1e-4)

    def test_pull_order_sorted_ids(self):
        """CSC (sorted-dst) edge order is trivially block-binned — the
        pull-side fast path needs no extra permutation."""
        rng = np.random.default_rng(7)
        e, v, b = 600, 128, 32
        ids = np.sort(rng.integers(0, v, e))
        bp = np.zeros(v // b + 1, np.int64)
        np.add.at(bp, ids // b + 1, 1)
        bp = np.cumsum(bp)
        vals = rng.standard_normal(e).astype(np.float32)
        red = BlockedSegmentReducer(ids, bp, v, b)
        got = np.asarray(red.sum(jnp.asarray(vals)))
        ref = np.asarray(segment_sum_ref(jnp.asarray(vals),
                                         jnp.asarray(ids), v))
        np.testing.assert_allclose(got, ref, atol=1e-4)

    @given(st.integers(1, 2000), st.integers(16, 400), st.integers(0, 3))
    @settings(max_examples=8, deadline=None)
    def test_sum_property(self, e, v, seed):
        rng = np.random.default_rng(seed)
        b = 64
        ids, bp = _binned(rng, e, v, b)
        vals = rng.standard_normal(e).astype(np.float32)
        red = BlockedSegmentReducer(ids, bp, v, b)
        got = np.asarray(red.sum(jnp.asarray(vals)))
        # total mass is conserved
        assert got.sum() == pytest.approx(vals.sum(), rel=1e-3, abs=1e-3)


def test_interpret_mode_follows_the_backend(monkeypatch):
    from repro.kernels.segment_reduce import kernel
    assert kernel._interpret(False) is False   # explicit choice wins
    for backend, want in (("cpu", True), ("tpu", False)):
        monkeypatch.setattr(kernel.jax, "default_backend", lambda: backend)
        assert kernel._interpret(None) is want
    monkeypatch.setattr(kernel.jax, "default_backend", lambda: "gpu")
    with pytest.raises(NotImplementedError):
        kernel._interpret(None)


class TestFlashAttention:
    @pytest.mark.parametrize("b,hq,hkv,sq,sk,d,causal", [
        (1, 2, 2, 128, 128, 64, True),
        (2, 4, 2, 256, 256, 64, True),
        (1, 8, 2, 128, 256, 128, True),   # GQA + kv longer than q
        (1, 2, 1, 64, 64, 32, False),
    ])
    def test_matches_ref(self, b, hq, hkv, sq, sk, d, causal):
        rng = np.random.default_rng(b + sq)
        q = jnp.asarray(rng.standard_normal((b, hq, sq, d), ).astype(np.float32))
        k = jnp.asarray(rng.standard_normal((b, hkv, sk, d)).astype(np.float32))
        v = jnp.asarray(rng.standard_normal((b, hkv, sk, d)).astype(np.float32))
        got = np.asarray(flash_attention(q, k, v, causal=causal, bq=64,
                                         bk=64))
        ref = np.asarray(gqa_ref(q, k, v, causal=causal))
        np.testing.assert_allclose(got, ref, atol=2e-3)

    def test_bf16(self):
        rng = np.random.default_rng(9)
        q = jnp.asarray(rng.standard_normal((1, 2, 64, 64)), jnp.bfloat16)
        k = jnp.asarray(rng.standard_normal((1, 2, 64, 64)), jnp.bfloat16)
        v = jnp.asarray(rng.standard_normal((1, 2, 64, 64)), jnp.bfloat16)
        got = np.asarray(flash_attention(q, k, v, bq=32, bk=32),
                         np.float32)
        ref = np.asarray(gqa_ref(q, k, v), np.float32)
        np.testing.assert_allclose(got, ref, atol=5e-2)

    def test_blocked_xla_matches_pallas(self):
        from repro.models.layers import gqa_attention
        rng = np.random.default_rng(3)
        q = jnp.asarray(rng.standard_normal((1, 4, 128, 64)).astype(np.float32))
        k = jnp.asarray(rng.standard_normal((1, 2, 128, 64)).astype(np.float32))
        v = jnp.asarray(rng.standard_normal((1, 2, 128, 64)).astype(np.float32))
        a = np.asarray(gqa_attention(q, k, v, causal=True))
        b = np.asarray(flash_attention(q, k, v, causal=True, bq=64, bk=64))
        np.testing.assert_allclose(a, b, atol=2e-3)


class TestEmbeddingBag:
    @pytest.mark.parametrize("r,d,b,p,mode", [
        (1000, 32, 16, 4, "sum"), (5000, 128, 33, 1, "sum"),
        (200, 64, 8, 8, "mean"), (50, 8, 3, 2, "sum"),
    ])
    def test_matches_oracle(self, r, d, b, p, mode):
        rng = np.random.default_rng(r + b)
        table = jnp.asarray(rng.standard_normal((r, d)).astype(np.float32))
        idx = jnp.asarray(rng.integers(0, r, (b, p)).astype(np.int32))
        got = np.asarray(embedding_bag_pallas(table, idx, mode=mode))
        ref = np.asarray(embedding_bag_ref(table, idx, mode=mode))
        np.testing.assert_allclose(got, ref, atol=1e-4)
