"""Per-kernel validation: shape/dtype sweeps asserting allclose against the
pure-jnp oracles in repro.kernels.ref (kernels run in interpret mode on CPU).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
# guarded import: hypothesis is optional, property tests skip without it
from _hypothesis_compat import given, settings, st

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mamba_scan import mamba_scan
from repro.kernels.moe_dispatch import compute_slots, moe_dispatch
from repro.kernels.rglru_scan import rglru_scan
from repro.kernels.segment_reduce import segment_sum


def _tol(dtype):
    return {"float32": 2e-5, "bfloat16": 2e-2}[jnp.dtype(dtype).name]


class TestFlashAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "B,Hq,Hkv,T,S,Dh,causal,window,qoff",
        [
            (2, 4, 2, 128, 128, 64, True, None, 0),
            (1, 8, 8, 100, 100, 32, True, None, 0),  # non-block-aligned
            (1, 4, 1, 64, 256, 64, True, None, 192),  # chunked decode offset
            (2, 4, 2, 128, 128, 64, True, 48, 0),  # sliding window
            (1, 2, 2, 96, 200, 128, False, None, 0),  # non-causal
            (1, 16, 4, 256, 256, 64, True, 128, 0),  # GQA + window
        ],
    )
    def test_matches_reference(self, dtype, B, Hq, Hkv, T, S, Dh, causal, window, qoff):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, Hq, T, Dh), dtype)
        k = jax.random.normal(ks[1], (B, Hkv, S, Dh), dtype)
        v = jax.random.normal(ks[2], (B, Hkv, S, Dh), dtype)
        out = flash_attention(
            q, k, v, causal=causal, window=window, q_offset=qoff,
            block_q=32, block_k=32,
        )
        expect = ref.attention_ref(q, k, v, causal=causal, window=window, q_offset=qoff)
        np.testing.assert_allclose(
            np.asarray(out, np.float32),
            np.asarray(expect, np.float32),
            atol=_tol(dtype), rtol=1e-2,
        )

    def test_block_shape_independence(self):
        """Output must not depend on the BlockSpec tiling."""
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (1, 4, 160, 64))
        k = jax.random.normal(ks[1], (1, 2, 160, 64))
        v = jax.random.normal(ks[2], (1, 2, 160, 64))
        outs = [
            flash_attention(q, k, v, block_q=bq, block_k=bk)
            for bq, bk in [(32, 32), (64, 32), (32, 80), (160, 160)]
        ]
        for o in outs[1:]:
            np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o), atol=2e-5)


class TestMambaScan:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "B,T,Di,Ds,chunk", [(2, 64, 32, 8, 16), (1, 100, 64, 16, 32), (1, 33, 16, 4, 16)]
    )
    def test_matches_reference(self, dtype, B, T, Di, Ds, chunk):
        ks = jax.random.split(jax.random.PRNGKey(0), 6)
        x = jax.random.normal(ks[0], (B, T, Di), dtype)
        delta = jax.nn.softplus(jax.random.normal(ks[1], (B, T, Di), dtype))
        A = -jax.nn.softplus(jax.random.normal(ks[2], (Di, Ds)))
        Bc = jax.random.normal(ks[3], (B, T, Ds), dtype)
        Cc = jax.random.normal(ks[4], (B, T, Ds), dtype)
        D = jax.random.normal(ks[5], (Di,))
        y, hT = mamba_scan(x, delta, A, Bc, Cc, D, chunk=chunk, block_d=Di)
        y_ref, hT_ref = ref.mamba_scan_ref(x, delta, A, Bc, Cc, D)
        np.testing.assert_allclose(
            np.asarray(y, np.float32), np.asarray(y_ref, np.float32),
            atol=_tol(dtype) * 5, rtol=3e-2,
        )
        np.testing.assert_allclose(
            np.asarray(hT), np.asarray(hT_ref), atol=_tol(dtype) * 5, rtol=3e-2
        )

    def test_stateful_equals_full(self):
        """Scanning two halves with carried state == scanning the whole."""
        ks = jax.random.split(jax.random.PRNGKey(2), 6)
        B, T, Di, Ds = 1, 64, 32, 8
        x = jax.random.normal(ks[0], (B, T, Di))
        delta = jax.nn.softplus(jax.random.normal(ks[1], (B, T, Di)))
        A = -jax.nn.softplus(jax.random.normal(ks[2], (Di, Ds)))
        Bc = jax.random.normal(ks[3], (B, T, Ds))
        Cc = jax.random.normal(ks[4], (B, T, Ds))
        D = jax.random.normal(ks[5], (Di,))
        y_full, h_full = mamba_scan(x, delta, A, Bc, Cc, D, chunk=16, block_d=Di)
        h = T // 2
        y1, s = mamba_scan(x[:, :h], delta[:, :h], A, Bc[:, :h], Cc[:, :h], D,
                           chunk=16, block_d=Di)
        y2, s2 = mamba_scan(x[:, h:], delta[:, h:], A, Bc[:, h:], Cc[:, h:], D,
                            h0=s, chunk=16, block_d=Di)
        np.testing.assert_allclose(
            np.asarray(jnp.concatenate([y1, y2], axis=1)), np.asarray(y_full),
            atol=1e-4, rtol=1e-4,
        )
        np.testing.assert_allclose(np.asarray(s2), np.asarray(h_full), atol=1e-4, rtol=1e-4)


class TestRGLRUScan:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("B,T,D,chunk", [(2, 64, 32, 16), (1, 100, 64, 32), (1, 50, 16, 64)])
    def test_matches_reference(self, dtype, B, T, D, chunk):
        ks = jax.random.split(jax.random.PRNGKey(0), 2)
        x = jax.random.normal(ks[0], (B, T, D), dtype)
        a = jax.nn.sigmoid(jax.random.normal(ks[1], (B, T, D), dtype))
        y, hT = rglru_scan(x, a, chunk=chunk, block_d=D)
        y_ref, hT_ref = ref.rglru_scan_ref(x, a)
        np.testing.assert_allclose(
            np.asarray(y, np.float32), np.asarray(y_ref, np.float32),
            atol=_tol(dtype) * 5, rtol=3e-2,
        )
        np.testing.assert_allclose(
            np.asarray(hT), np.asarray(hT_ref), atol=_tol(dtype) * 5, rtol=3e-2
        )

    def test_stateful_equals_full(self):
        ks = jax.random.split(jax.random.PRNGKey(1), 2)
        B, T, D = 1, 48, 32
        x = jax.random.normal(ks[0], (B, T, D))
        a = jax.nn.sigmoid(jax.random.normal(ks[1], (B, T, D)))
        y_full, h_full = rglru_scan(x, a, chunk=16, block_d=D)
        y1, s = rglru_scan(x[:, :24], a[:, :24], chunk=16, block_d=D)
        y2, s2 = rglru_scan(x[:, 24:], a[:, 24:], h0=s, chunk=16, block_d=D)
        np.testing.assert_allclose(
            np.asarray(jnp.concatenate([y1, y2], axis=1)), np.asarray(y_full),
            atol=1e-5,
        )
        np.testing.assert_allclose(np.asarray(s2), np.asarray(h_full), atol=1e-5)


class TestSegmentSum:
    @settings(max_examples=15, deadline=None)
    @given(
        n=st.integers(4, 300),
        d=st.sampled_from([4, 16, 33]),
        s=st.integers(2, 20),
        seed=st.integers(0, 100),
        block=st.sampled_from([16, 64, 512]),
    )
    def test_matches_reference(self, n, d, s, seed, block):
        rng = np.random.default_rng(seed)
        values = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        ids = jnp.asarray(np.sort(rng.integers(0, s, size=n)).astype(np.int32))
        out = segment_sum(values, ids, s, block_n=block)
        expect = ref.segment_sum_ref(values, ids, s)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=1e-4)

    def test_unsorted_ids_still_correct(self):
        rng = np.random.default_rng(0)
        values = jnp.asarray(rng.normal(size=(128, 8)).astype(np.float32))
        ids = jnp.asarray(rng.integers(0, 7, size=128).astype(np.int32))
        out = segment_sum(values, ids, 7, block_n=32)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref.segment_sum_ref(values, ids, 7)), atol=1e-4
        )

    def test_many_segments_shrink_the_row_block_and_stay_exact(self):
        """5,000 segments shrink the row block to fit the one-hot budget;
        integer counts up to 2^20 still sum exactly (fp32 contraction)."""
        rng = np.random.default_rng(1)
        n, s = 700, 5000
        ids = np.sort(rng.integers(0, s, size=n)).astype(np.int32)
        counts = rng.integers(1, 1 << 20, size=n).astype(np.float32)
        out = segment_sum(jnp.asarray(counts[:, None]), jnp.asarray(ids), s)
        expect = np.bincount(ids, weights=counts.astype(np.int64), minlength=s)
        np.testing.assert_array_equal(np.asarray(out)[:, 0], expect)


class TestMoEDispatch:
    @pytest.mark.parametrize("T,D,E,C", [(128, 32, 4, 40), (200, 64, 8, 16), (64, 16, 3, 64)])
    def test_matches_reference(self, T, D, E, C):
        ks = jax.random.split(jax.random.PRNGKey(0), 2)
        tokens = jax.random.normal(ks[0], (T, D))
        eids = jax.random.randint(ks[1], (T,), 0, E)
        slots = compute_slots(eids, E)
        out = moe_dispatch(tokens, eids, slots, E, C, block_t=48)
        expect = ref.moe_dispatch_ref(tokens, eids, slots, E, C)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=1e-5)

    def test_capacity_overflow_drops(self):
        # all tokens to expert 0 with capacity 4: only first 4 survive
        tokens = jnp.arange(80, dtype=jnp.float32).reshape(8, 10)
        eids = jnp.zeros(8, jnp.int32)
        slots = compute_slots(eids, 2)
        out = moe_dispatch(tokens, eids, slots, 2, 4, block_t=8)
        np.testing.assert_allclose(np.asarray(out[0]), np.asarray(tokens[:4]))
        assert float(jnp.abs(out[1]).sum()) == 0.0

    def test_roundtrip_dispatch_combine(self):
        """dispatch → identity expert → combine reproduces gated tokens."""
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        T, D, E, C = 96, 16, 4, 32  # capacity ample: no drops
        tokens = jax.random.normal(ks[0], (T, D))
        eids = jax.random.randint(ks[1], (T,), 0, E)
        gates = jax.nn.sigmoid(jax.random.normal(ks[2], (T,)))
        buf, slots = ops.dispatch_tokens(tokens, eids, E, C)
        back = ops.combine_tokens(buf, eids, slots, gates, C)
        np.testing.assert_allclose(
            np.asarray(back), np.asarray(tokens * gates[:, None]), atol=1e-5
        )


class TestOpsFallback:
    def test_small_shapes_use_reference(self):
        """Tiny inputs route to the reference and still agree with it."""
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (1, 2, 8, 16))
        k = jax.random.normal(ks[1], (1, 2, 8, 16))
        v = jax.random.normal(ks[2], (1, 2, 8, 16))
        np.testing.assert_allclose(
            np.asarray(ops.attention(q, k, v)),
            np.asarray(ref.attention_ref(q, k, v)),
            atol=1e-6,
        )

    def test_segment_sum_path_names_what_runs(self):
        assert ops.segment_sum_path(ops._MIN_KERNEL_SEQ - 1) == "reference"
        assert ops.segment_sum_path(4096, use_kernel=False) == "reference"
        assert ops.segment_sum_path(4096) == "pallas-interpret"  # CPU suite


def test_interpret_mode_only_on_cpu(monkeypatch):
    """Kernels compile on TPU, interpret on CPU, and refuse anything else
    rather than silently interpreting on an accelerator."""
    from repro.kernels import interpret_mode

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert interpret_mode() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert interpret_mode() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        interpret_mode()
    with pytest.raises(RuntimeError, match="gpu"):
        segment_sum(jnp.ones((8, 1)), jnp.zeros(8, jnp.int32), 2)
