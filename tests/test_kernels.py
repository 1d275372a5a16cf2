"""Pallas-kernel validation: interpret=True vs the pure-jnp oracles,
swept over shapes and dtypes (+ hypothesis property sweeps).  Compiles for
the TPU itself are in test_tpu_compile.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property sweeps need hypothesis")
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.persistent_matmul import persistent_matmul
from repro.kernels.selective_scan import selective_scan


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


class TestPersistentMatmul:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "m,k,n,bands", [(256, 128, 256, 2), (512, 256, 512, 4), (128, 384, 256, 1)]
    )
    def test_matches_ref(self, m, k, n, bands, dtype):
        kx, kw = jax.random.split(jax.random.PRNGKey(0))
        x = _rand(kx, (m, k), dtype)
        w = _rand(kw, (k, n), dtype)
        got = persistent_matmul(x, w, n_bands=bands, interpret=True)
        want = ref.matmul_ref(x, w)
        tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=tol, atol=tol * 8,
        )

    def test_band_partition_invariance(self):
        """Pinning bands is a schedule, not a math change: any band count
        gives identical results (the paper's SM-allocation transparency)."""
        kx, kw = jax.random.split(jax.random.PRNGKey(1))
        x = _rand(kx, (1024, 128), jnp.float32)
        w = _rand(kw, (128, 512), jnp.float32)
        outs = [
            np.asarray(persistent_matmul(x, w, n_bands=b, interpret=True))
            for b in (1, 2, 4, 8)
        ]
        for o in outs[1:]:
            np.testing.assert_allclose(o, outs[0], rtol=1e-6)

    def test_ops_fallback_for_odd_shapes(self):
        """A tiling that cannot split over bands x 2 lanes raises; there is
        no silent ``x @ w`` fallback."""
        kx, kw = jax.random.split(jax.random.PRNGKey(2))
        x = _rand(kx, (96, 80), jnp.float32)
        w = _rand(kw, (80, 112), jnp.float32)
        with pytest.raises(ValueError, match="cannot split"):
            ops.pinned_matmul(x, w, interpret=True)

    def test_ops_pinned_matmul_matches_ref(self):
        kx, kw = jax.random.split(jax.random.PRNGKey(6))
        x = _rand(kx, (256, 256), jnp.float32)
        w = _rand(kw, (256, 384), jnp.float32)
        got = ops.pinned_matmul(x, w, interpret=True)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref.matmul_ref(x, w)),
            rtol=1e-4, atol=1e-4,
        )


class TestFlashAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("s,qb", [(256, 128), (512, 256), (384, 128)])
    def test_causal_matches_ref(self, s, qb, dtype):
        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        bh, hd = 4, 64
        q = _rand(keys[0], (bh, s, hd), dtype)
        k = _rand(keys[1], (bh, s, hd), dtype)
        v = _rand(keys[2], (bh, s, hd), dtype)
        got = flash_attention(
            q, k, v, scale=hd ** -0.5, q_block=qb, kv_block=qb, interpret=True
        )
        want = ref.flash_attention_ref(q, k, v, scale=hd ** -0.5)
        tol = 3e-2 if dtype == jnp.bfloat16 else 2e-4
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=tol, atol=tol,
        )

    @pytest.mark.parametrize("window", [64, 128, 300])
    def test_sliding_window(self, window):
        keys = jax.random.split(jax.random.PRNGKey(3), 3)
        bh, s, hd = 2, 256, 32
        q = _rand(keys[0], (bh, s, hd), jnp.float32)
        k = _rand(keys[1], (bh, s, hd), jnp.float32)
        v = _rand(keys[2], (bh, s, hd), jnp.float32)
        got = flash_attention(
            q, k, v, scale=hd ** -0.5, window=window,
            q_block=128, kv_block=128, interpret=True,
        )
        want = ref.flash_attention_ref(q, k, v, scale=hd ** -0.5, window=window)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
        )

    def test_gqa_expansion_via_ops(self):
        keys = jax.random.split(jax.random.PRNGKey(4), 3)
        b, s, h, hkv, hd = 2, 256, 8, 2, 32
        q = _rand(keys[0], (b, s, h, hd), jnp.float32)
        k = _rand(keys[1], (b, s, hkv, hd), jnp.float32)
        v = _rand(keys[2], (b, s, hkv, hd), jnp.float32)
        got = ops.mha_flash(q, k, v, scale=hd ** -0.5, interpret=True)
        # oracle: expand kv then per-head attention
        kx = jnp.repeat(k, h // hkv, axis=2)
        vx = jnp.repeat(v, h // hkv, axis=2)
        qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
        kf = kx.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
        vf = vx.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
        want = ref.flash_attention_ref(qf, kf, vf, scale=hd ** -0.5)
        want = want.reshape(b, h, s, hd).transpose(0, 2, 1, 3).reshape(b, s, h * hd)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
        )

    def test_matches_model_attention_path(self):
        """Kernel == models/attention._flash_sdpa == small-path softmax."""
        from repro.models.attention import _flash_sdpa

        keys = jax.random.split(jax.random.PRNGKey(5), 3)
        b, s, h, hd = 2, 512, 4, 32
        q = _rand(keys[0], (b, s, h, hd), jnp.float32)
        k = _rand(keys[1], (b, s, h, hd), jnp.float32)
        v = _rand(keys[2], (b, s, h, hd), jnp.float32)
        jnp_flash = _flash_sdpa(q, k, v, hd ** -0.5, None, q_block=128, kv_block=128)
        kern = ops.mha_flash(q, k, v, scale=hd ** -0.5, interpret=True)
        np.testing.assert_allclose(
            np.asarray(jnp_flash), np.asarray(kern), rtol=2e-4, atol=2e-4
        )


class TestSelectiveScan:
    @pytest.mark.parametrize("s,d,n", [(64, 32, 8), (128, 64, 16), (96, 48, 4)])
    def test_matches_ref(self, s, d, n):
        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        b = 2
        abar = jax.nn.sigmoid(_rand(keys[0], (b, s, d, n), jnp.float32))  # stable
        bx = _rand(keys[1], (b, s, d, n), jnp.float32) * 0.1
        c = _rand(keys[2], (b, s, n), jnp.float32)
        got = selective_scan(abar, bx, c, chunk=32, d_block=16, interpret=True)
        want = ref.selective_scan_ref(abar, bx, c)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4
        )

    @pytest.mark.parametrize("s,d,n", [(64, 256, 16), (48, 128, 4)])
    def test_ops_mamba_scan_matches_ref(self, s, d, n):
        """The wrapper's VMEM-sized chunk and d-block give the oracle's y."""
        keys = jax.random.split(jax.random.PRNGKey(7), 3)
        abar = jax.nn.sigmoid(_rand(keys[0], (1, s, d, n), jnp.float32))
        bx = _rand(keys[1], (1, s, d, n), jnp.float32) * 0.1
        c = _rand(keys[2], (1, s, n), jnp.float32)
        got = ops.mamba_scan(abar, bx, c, interpret=True)
        want = ref.selective_scan_ref(abar, bx, c)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4
        )

    def test_non_dividing_blocks_raise(self):
        abar = jnp.ones((1, 40, 32, 4), jnp.float32)
        with pytest.raises(ValueError, match="must divide"):
            selective_scan(abar, abar, jnp.ones((1, 40, 4)), chunk=16,
                           d_block=16, interpret=True)

    def test_matches_model_mamba_path(self):
        """Kernel result == models/mamba.ssm_scan_chunked (modulo d_skip)."""
        import dataclasses

        from repro.configs import get_smoke_config
        from repro.models.mamba import _ssm_params, init_mamba, ssm_scan_chunked

        cfg = get_smoke_config("jamba-v0.1-52b")
        params = init_mamba(jax.random.PRNGKey(0), cfg, jnp.float32)
        b, s = 2, 64
        xc = jax.random.normal(jax.random.PRNGKey(1), (b, s, cfg.d_inner)) * 0.1
        abar, bx, c_t = _ssm_params(params, xc)
        y_model, _ = ssm_scan_chunked(params, xc, chunk=16)
        y_model = y_model - xc.astype(jnp.float32) * params["d_skip"]  # strip skip
        y_kernel = selective_scan(abar, bx, c_t.astype(jnp.float32),
                                  chunk=16, d_block=64, interpret=True)
        np.testing.assert_allclose(
            np.asarray(y_model), np.asarray(y_kernel), rtol=1e-4, atol=1e-4
        )


@st.composite
def _attn_case(draw):
    s = draw(st.sampled_from([128, 256]))
    hd = draw(st.sampled_from([16, 32, 64]))
    bh = draw(st.integers(1, 3))
    window = draw(st.sampled_from([None, 64, 100]))
    seed = draw(st.integers(0, 2**16))
    return s, hd, bh, window, seed


class TestFlashProperty:
    @settings(max_examples=12, deadline=None)
    @given(case=_attn_case())
    def test_flash_property_sweep(self, case):
        s, hd, bh, window, seed = case
        keys = jax.random.split(jax.random.PRNGKey(seed), 3)
        q = _rand(keys[0], (bh, s, hd), jnp.float32)
        k = _rand(keys[1], (bh, s, hd), jnp.float32)
        v = _rand(keys[2], (bh, s, hd), jnp.float32)
        got = flash_attention(
            q, k, v, scale=hd ** -0.5, window=window,
            q_block=64, kv_block=64, interpret=True,
        )
        want = ref.flash_attention_ref(q, k, v, scale=hd ** -0.5, window=window)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=3e-4, atol=3e-4
        )
