"""Model-layer unit tests: attention, RoPE, SSD, xLSTM, MoE invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.ref import ssd_chunk_ref
from repro.models import attention as attn
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models import xlstm as xlstm_lib
from repro.models.modules import (apply_rope, flatten_updates, rmsnorm,
                                  init_rmsnorm, unflatten_like)


class TestRoPE:
    def test_norm_preserving(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 4, 64))
        pos = jnp.arange(8)[None, :]
        y = apply_rope(x, pos)
        np.testing.assert_allclose(np.linalg.norm(np.asarray(x), axis=-1),
                                   np.linalg.norm(np.asarray(y), axis=-1),
                                   rtol=1e-5)

    def test_relative_property(self):
        """<rope(q,i), rope(k,j)> depends only on i-j."""
        q = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 1, 32))
        k = jax.random.normal(jax.random.PRNGKey(2), (1, 1, 1, 32))
        def dot_at(i, j):
            qi = apply_rope(q, jnp.array([[i]]))
            kj = apply_rope(k, jnp.array([[j]]))
            return float(jnp.sum(qi * kj))
        assert dot_at(3, 1) == pytest.approx(dot_at(10, 8), abs=1e-4)
        assert dot_at(5, 5) == pytest.approx(dot_at(0, 0), abs=1e-4)

    def test_position_zero_identity(self):
        x = jax.random.normal(jax.random.PRNGKey(3), (1, 1, 2, 16))
        y = apply_rope(x, jnp.zeros((1, 1), jnp.int32))
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-6)


class TestRMSNorm:
    @given(st.integers(0, 100))
    @settings(max_examples=10, deadline=None)
    def test_unit_rms(self, seed):
        p = init_rmsnorm(32)
        x = jax.random.normal(jax.random.PRNGKey(seed), (4, 32)) * 10
        y = np.asarray(rmsnorm(p, x))
        rms = np.sqrt((y ** 2).mean(-1))
        np.testing.assert_allclose(rms, 1.0, rtol=1e-3)

    def test_scale_invariance(self):
        p = init_rmsnorm(16)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 16))
        np.testing.assert_allclose(np.asarray(rmsnorm(p, x)),
                                   np.asarray(rmsnorm(p, x * 100)), atol=1e-4)


class TestAttention:
    def test_gqa_repeat_equals_mha_when_equal_heads(self):
        """kv == heads: GQA path is plain MHA."""
        key = jax.random.PRNGKey(0)
        p = attn.init_attention(key, 64, 4, 4, 16)
        x = jax.random.normal(key, (2, 8, 64))
        y = attn.attention_fwd(p, x, n_heads=4, n_kv=4, head_dim=16,
                               rope_theta=None)
        assert y.shape == (2, 8, 64)

    def test_causality(self):
        """Changing future tokens must not change past outputs."""
        key = jax.random.PRNGKey(1)
        p = attn.init_attention(key, 32, 2, 1, 16)
        x1 = jax.random.normal(key, (1, 8, 32))
        x2 = x1.at[:, 5:].set(jax.random.normal(jax.random.fold_in(key, 1),
                                                (1, 3, 32)))
        kw = dict(n_heads=2, n_kv=1, head_dim=16, rope_theta=10000.0)
        y1 = attn.attention_fwd(p, x1, **kw)
        y2 = attn.attention_fwd(p, x2, **kw)
        np.testing.assert_allclose(np.asarray(y1[:, :5]),
                                   np.asarray(y2[:, :5]), atol=1e-5)

    def test_window_restricts_reach(self):
        """With window w, token t ignores tokens < t-w+1."""
        key = jax.random.PRNGKey(2)
        p = attn.init_attention(key, 32, 2, 2, 16)
        x1 = jax.random.normal(key, (1, 16, 32))
        x2 = x1.at[:, 0:2].set(0.0)        # far past
        kw = dict(n_heads=2, n_kv=2, head_dim=16, rope_theta=None, window=4)
        y1 = attn.attention_fwd(p, x1, **kw)
        y2 = attn.attention_fwd(p, x2, **kw)
        np.testing.assert_allclose(np.asarray(y1[:, 10:]),
                                   np.asarray(y2[:, 10:]), atol=1e-5)

    def test_decode_matches_full_forward(self):
        """Token-by-token decode with positional cache == full causal fwd."""
        key = jax.random.PRNGKey(3)
        D, H, KV, hd, S, B = 32, 2, 1, 16, 6, 2
        p = attn.init_attention(key, D, H, KV, hd)
        x = jax.random.normal(key, (B, S, D))
        full = attn.attention_fwd(p, x, n_heads=H, n_kv=KV, head_dim=hd,
                                  rope_theta=10000.0)
        cache = attn.init_kv_cache(B, S, KV, hd, jnp.float32)
        outs = []
        for t in range(S):
            y, cache = attn.attention_decode(
                p, cache, x[:, t:t + 1], jnp.full((B,), t), n_heads=H,
                n_kv=KV, head_dim=hd, rope_theta=10000.0)
            outs.append(y)
        dec = jnp.concatenate(outs, axis=1)
        np.testing.assert_allclose(np.asarray(full), np.asarray(dec),
                                   atol=1e-4, rtol=1e-4)

    def test_ring_buffer_decode_matches_windowed_forward(self):
        """Windowed ring-buffer cache == full forward with the same window."""
        key = jax.random.PRNGKey(4)
        D, H, KV, hd, S, B, W = 32, 2, 2, 16, 10, 1, 4
        p = attn.init_attention(key, D, H, KV, hd)
        x = jax.random.normal(key, (B, S, D))
        full = attn.attention_fwd(p, x, n_heads=H, n_kv=KV, head_dim=hd,
                                  rope_theta=10000.0, window=W)
        cache = attn.init_kv_cache(B, W, KV, hd, jnp.float32)
        outs = []
        for t in range(S):
            y, cache = attn.attention_decode(
                p, cache, x[:, t:t + 1], jnp.full((B,), t), n_heads=H,
                n_kv=KV, head_dim=hd, rope_theta=10000.0, window=W)
            outs.append(y)
        dec = jnp.concatenate(outs, axis=1)
        np.testing.assert_allclose(np.asarray(full), np.asarray(dec),
                                   atol=1e-4, rtol=1e-4)


class TestMLA:
    def test_decode_matches_forward(self):
        """Absorbed-matrix decode == expanded training attention."""
        key = jax.random.PRNGKey(5)
        D, H, S, B = 32, 2, 5, 2
        kw = dict(n_heads=H, qk_nope=8, qk_rope=8, v_dim=8, kv_rank=16,
                  rope_theta=10000.0)
        p = attn.init_mla(key, D, H, q_rank=16, kv_rank=16, qk_nope=8,
                          qk_rope=8, v_dim=8)
        x = jax.random.normal(key, (B, S, D))
        full = attn.mla_fwd(p, x, **kw)
        cache = attn.init_mla_cache(B, S, 16, 8, jnp.float32)
        outs = []
        for t in range(S):
            y, cache = attn.mla_decode(p, cache, x[:, t:t + 1],
                                       jnp.full((B,), t), **kw)
            outs.append(y)
        dec = jnp.concatenate(outs, axis=1)
        np.testing.assert_allclose(np.asarray(full), np.asarray(dec),
                                   atol=1e-4, rtol=1e-4)


class TestSSD:
    def test_chunked_matches_recurrence(self):
        """Chunked SSD == step-by-step recurrence (oracle)."""
        key = jax.random.PRNGKey(6)
        b, l, h, p, n = 2, 32, 3, 8, 4
        ks = jax.random.split(key, 4)
        X = jax.random.normal(ks[0], (b, l, h, p))
        dtA = -jax.nn.softplus(jax.random.normal(ks[1], (b, l, h)))
        B = jax.random.normal(ks[2], (b, l, h, n))
        C = jax.random.normal(ks[3], (b, l, h, n))
        for chunk in (4, 8, 16, 32):
            Y, fin = ssm_lib.ssd_chunked(X, dtA, B, C, chunk)
            Yr, finr = ssd_chunk_ref(X, dtA, B, C)
            np.testing.assert_allclose(np.asarray(Y), np.asarray(Yr),
                                       atol=1e-4, rtol=1e-4)
            np.testing.assert_allclose(np.asarray(fin), np.asarray(finr),
                                       atol=1e-4, rtol=1e-4)

    def test_mamba_block_decode_matches_forward(self):
        key = jax.random.PRNGKey(7)
        D, S, B = 16, 12, 2
        kw = dict(d_state=4, expand=2, head_dim=8)
        p = ssm_lib.init_mamba2(key, D, d_state=4, expand=2, head_dim=8)
        x = jax.random.normal(key, (B, S, D))
        full = ssm_lib.mamba2_fwd(p, x, chunk=4, **kw)
        cache = ssm_lib.init_mamba2_cache(B, D, d_state=4, expand=2,
                                          head_dim=8)
        outs = []
        for t in range(S):
            y, cache = ssm_lib.mamba2_step(p, cache, x[:, t:t + 1], **kw)
            outs.append(y)
        dec = jnp.concatenate(outs, axis=1)
        np.testing.assert_allclose(np.asarray(full), np.asarray(dec),
                                   atol=1e-4, rtol=1e-4)


class TestXLSTM:
    def test_mlstm_block_decode_matches_forward(self):
        key = jax.random.PRNGKey(8)
        D, S, B, H = 16, 10, 2, 2
        p = xlstm_lib.init_mlstm(key, D, H)
        x = jax.random.normal(key, (B, S, D))
        full = xlstm_lib.mlstm_block_fwd(p, x, n_heads=H, chunk=5)
        cache = xlstm_lib.init_mlstm_cache(B, D, H)
        outs = []
        for t in range(S):
            y, cache = xlstm_lib.mlstm_block_step(p, cache, x[:, t:t + 1],
                                                  n_heads=H)
            outs.append(y)
        dec = jnp.concatenate(outs, axis=1)
        np.testing.assert_allclose(np.asarray(full), np.asarray(dec),
                                   atol=1e-4, rtol=1e-4)

    def test_slstm_block_decode_matches_forward(self):
        key = jax.random.PRNGKey(9)
        D, S, B, H = 16, 10, 2, 2
        p = xlstm_lib.init_slstm(key, D, H)
        x = jax.random.normal(key, (B, S, D))
        full = xlstm_lib.slstm_block_fwd(p, x, n_heads=H, chunk=5)
        cache = xlstm_lib.init_slstm_cache(B, D)
        outs = []
        for t in range(S):
            y, cache = xlstm_lib.slstm_block_step(p, cache, x[:, t:t + 1],
                                                  n_heads=H)
            outs.append(y)
        dec = jnp.concatenate(outs, axis=1)
        np.testing.assert_allclose(np.asarray(full), np.asarray(dec),
                                   atol=1e-4, rtol=1e-4)

    def test_mlstm_chunk_invariance(self):
        key = jax.random.PRNGKey(10)
        p = xlstm_lib.init_mlstm(key, 16, 2)
        x = jax.random.normal(key, (1, 16, 16))
        a = xlstm_lib.mlstm_block_fwd(p, x, n_heads=2, chunk=4)
        b = xlstm_lib.mlstm_block_fwd(p, x, n_heads=2, chunk=16)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


class TestMoE:
    def _apply(self, key, N=64, D=16, E=4, k=2, cf=8.0):
        p = moe_lib.init_moe(key, D, 32, E)
        x = jax.random.normal(key, (1, N, D))
        return p, x, moe_lib.moe_apply(p, x, top_k=k, capacity_factor=cf)

    def test_output_shape_finite(self):
        p, x, (y, aux) = self._apply(jax.random.PRNGKey(0))
        assert y.shape == x.shape
        assert np.all(np.isfinite(np.asarray(y)))

    def test_load_balance_loss_near_one_for_uniform(self):
        """Uniform routing -> load balance loss == E * sum(1/E * 1/E * E) = 1."""
        key = jax.random.PRNGKey(1)
        p = moe_lib.init_moe(key, 8, 16, 4)
        p = dict(p, router=jnp.zeros_like(p["router"]))  # uniform logits
        x = jax.random.normal(key, (1, 256, 8))
        _, aux = moe_lib.moe_apply(p, x, top_k=2, capacity_factor=8.0)
        # with ties broken arbitrarily the top-1 histogram may deviate a bit
        assert 0.5 < float(aux.load_balance_loss) < 2.0

    def test_expert_load_sums_to_one(self):
        _, _, (y, aux) = self._apply(jax.random.PRNGKey(2))
        assert float(jnp.sum(aux.expert_load)) == pytest.approx(1.0, abs=1e-5)

    def test_capacity_drops_dont_crash(self):
        """Tiny capacity factor: tokens dropped, output still finite."""
        p, x, (y, aux) = self._apply(jax.random.PRNGKey(3), cf=0.25)
        assert np.all(np.isfinite(np.asarray(y)))

    def test_matches_dense_computation_with_big_capacity(self):
        """With capacity >= all tokens, dispatch-combine == dense masked sum."""
        key = jax.random.PRNGKey(4)
        D, E, k = 8, 4, 2
        p = moe_lib.init_moe(key, D, 16, E)
        x = jax.random.normal(key, (1, 32, D))
        y, _ = moe_lib.moe_apply(p, x, top_k=k, capacity_factor=100.0)

        # dense reference
        xt = x.reshape(-1, D)
        logits = xt @ p["router"]
        probs = jax.nn.softmax(logits, -1)
        gv, ei = jax.lax.top_k(probs, k)
        gv = gv / gv.sum(-1, keepdims=True)
        y_ref = jnp.zeros_like(xt)
        for e in range(E):
            up = xt @ p["w_up"][e]
            g = jax.nn.silu(xt @ p["w_gate"][e])
            out_e = (g * up) @ p["w_down"][e]
            w = jnp.sum(jnp.where(ei == e, gv, 0.0), -1)
            y_ref = y_ref + out_e * w[:, None]
        np.testing.assert_allclose(np.asarray(y[0]), np.asarray(y_ref),
                                   atol=1e-4, rtol=1e-4)


class TestFlatten:
    @given(st.integers(0, 50))
    @settings(max_examples=10, deadline=None)
    def test_roundtrip(self, seed):
        key = jax.random.PRNGKey(seed)
        tree = {"a": jax.random.normal(key, (3, 4)),
                "b": {"c": jax.random.normal(jax.random.fold_in(key, 1), (5,)),
                      "d": jax.random.normal(jax.random.fold_in(key, 2), (2, 2, 2))}}
        flat = flatten_updates(tree)
        assert flat.shape == (3 * 4 + 5 + 8,)
        back = unflatten_like(flat, tree)
        for a, b in zip(jax.tree_util.tree_leaves(tree),
                        jax.tree_util.tree_leaves(back)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b))
