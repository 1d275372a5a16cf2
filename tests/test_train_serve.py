"""Integration tests: training reduces loss; checkpoint round-trip;
serving engine decodes; data pipeline contracts."""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.data import DataConfig, TokenPipeline
from repro.models import LayerSpec, Model, ModelConfig
from repro.serving import ServeConfig, ServingEngine
from repro.train.checkpoint import latest_step, load_checkpoint, save_checkpoint
from repro.train.optimizer import AdamWConfig, adamw_update, init_opt_state


def tiny_cfg(vocab=256):
    return ModelConfig(
        name="tiny", arch_type="dense", d_model=64, n_heads=4, n_kv_heads=2,
        d_ff=128, vocab=vocab, pattern=(LayerSpec("attn", "mlp"),),
        n_repeats=2, tie_embeddings=True, dtype="float32",
    )


class TestDataPipeline:
    def test_shapes_and_labels_shift(self):
        p = TokenPipeline(DataConfig(vocab=100, seq_len=16, global_batch=4))
        toks, labels = p.batch(0)
        assert toks.shape == labels.shape == (4, 16)
        assert toks.dtype == np.int32
        assert (toks >= 0).all() and (toks < 100).all()

    def test_deterministic_per_step(self):
        p = TokenPipeline(DataConfig(vocab=100, seq_len=16, global_batch=4))
        a, _ = p.batch(3)
        b, _ = p.batch(3)
        np.testing.assert_array_equal(a, b)
        c, _ = p.batch(4)
        assert not np.array_equal(a, c)

    def test_host_sharding_disjoint_draws(self):
        h0 = TokenPipeline(DataConfig(100, 16, 8, n_hosts=2, host_id=0))
        h1 = TokenPipeline(DataConfig(100, 16, 8, n_hosts=2, host_id=1))
        a, _ = h0.batch(0)
        b, _ = h1.batch(0)
        assert a.shape == (4, 16)
        assert not np.array_equal(a, b)

    def test_learnable_structure(self):
        """bigram structure => next-token is predictable 80% of the time"""
        p = TokenPipeline(DataConfig(vocab=50, seq_len=64, global_batch=8))
        toks, labels = p.batch(0)
        follows = p._next[toks]
        agreement = (follows == labels).mean()
        assert agreement > 0.6


class TestTraining:
    def test_loss_decreases(self):
        cfg = tiny_cfg()
        model = Model(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        opt_cfg = AdamWConfig(lr=3e-3, warmup_steps=2, total_steps=30)
        opt_state = init_opt_state(params)
        data = TokenPipeline(DataConfig(cfg.vocab, 32, 8))

        @jax.jit
        def step(params, opt_state, t, l):
            loss, g = jax.value_and_grad(lambda p: model.loss(p, t, l))(params)
            params, opt_state, _ = adamw_update(opt_cfg, params, g, opt_state)
            return params, opt_state, loss

        losses = []
        for i in range(30):
            t, l = data.batch(i)
            params, opt_state, loss = step(params, opt_state,
                                           jnp.asarray(t), jnp.asarray(l))
            losses.append(float(loss))
        assert losses[-1] < losses[0] - 0.5, losses[::6]

    def test_checkpoint_roundtrip(self, tmp_path):
        cfg = tiny_cfg()
        model = Model(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        opt = init_opt_state(params)
        save_checkpoint(tmp_path, 7, params, opt)
        assert latest_step(tmp_path) == 7
        step, p2, o2 = load_checkpoint(
            tmp_path / "step_00000007.msgpack", params, opt
        )
        assert step == 7
        for a, b in zip(jax.tree_util.tree_leaves(params),
                        jax.tree_util.tree_leaves(p2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestServingEngine:
    def test_generate_shapes_and_determinism(self):
        cfg = tiny_cfg()
        eng = ServingEngine(cfg, ServeConfig(max_context=64, batch=2))
        prompts = np.random.default_rng(0).integers(
            0, cfg.vocab, (2, 16)
        ).astype(np.int32)
        out1, stats = eng.generate(prompts, max_new_tokens=5)
        out2, _ = eng.generate(prompts, max_new_tokens=5)
        assert out1.shape == (2, 5)
        np.testing.assert_array_equal(out1, out2)  # greedy => deterministic
        assert stats["tokens"] == 10

    def test_decode_matches_forward(self):
        """Greedy decode via cache == argmax of the full forward logits."""
        cfg = tiny_cfg()
        model = Model(cfg)
        params = model.init_params(jax.random.PRNGKey(1))
        b, s = 2, 12
        toks = jax.random.randint(jax.random.PRNGKey(2), (b, s), 0, cfg.vocab)
        # full forward: logits at the last position
        hidden, _ = model.forward_train(params, toks)
        full_logits = model._logits(params, hidden[:, -1:])
        # cache path
        caches = model.init_caches(b, 32)
        pre_logits, caches, _ = model.prefill(params, toks, caches)
        np.testing.assert_allclose(
            np.asarray(full_logits), np.asarray(pre_logits), rtol=2e-4, atol=2e-4
        )


# tiny configurations of every input path the engine's programs take
SERVE_CFGS = {
    "dense_gqa": tiny_cfg(),
    "patches": dataclasses.replace(tiny_cfg(), n_patches=4),
    "enc_dec": dataclasses.replace(tiny_cfg(), n_enc_layers=1, enc_ctx=8),
}


def _embeds(cfg, b, key):
    """Random patch / encoder embeddings the configuration takes, as
    ``generate``'s keyword arguments."""
    kw = {}
    if cfg.n_patches:
        kw["extra_embeds"] = 0.02 * jax.random.normal(
            key, (b, cfg.n_patches, cfg.d_model))
    if cfg.is_encoder_decoder:
        kw["enc_embeds"] = 0.02 * jax.random.normal(
            jax.random.fold_in(key, 1), (b, cfg.enc_ctx, cfg.d_model))
    return kw


def eager_loop(model, params, prompts, n, max_context, fed=None, **kw):
    """The serving loop written out op by op: prefill, then ``n`` decode
    steps through the cache, each token the argmax of the last logits, or
    the token in ``fed`` when given.  Returns the tokens ``[B, n]`` and the
    logits each was chosen from ``[n, B, V]``."""
    b, s = prompts.shape
    cfg = model.cfg
    caches = model.init_caches(b, max_context)
    logits, caches, _ = model.prefill(params, jnp.asarray(prompts), caches,
                                      **kw)
    cache_len = jnp.full((b,), s + cfg.n_patches, jnp.int32)
    toks, seen = [], []
    for i in range(n):
        last = logits[:, -1, :]
        tok = (jnp.argmax(last, axis=-1).astype(jnp.int32) if fed is None
               else jnp.asarray(fed[:, i]))
        toks.append(np.asarray(tok))
        seen.append(np.asarray(last))
        logits, caches = model.decode_step(params, tok[:, None], caches,
                                           cache_len)
        cache_len = cache_len + 1
    return np.stack(toks, axis=1), np.stack(seen)


class TestSamplingOnDevice:
    B, S, N, CTX = 2, 10, 5, 32

    @pytest.mark.parametrize("name", list(SERVE_CFGS))
    def test_greedy_matches_the_eager_loop(self, name):
        cfg = SERVE_CFGS[name]
        eng = ServingEngine(cfg, ServeConfig(max_context=self.CTX,
                                             batch=self.B), seed=3)
        prompts = np.random.default_rng(4).integers(
            0, cfg.vocab, (self.B, self.S)).astype(np.int32)
        kw = _embeds(cfg, self.B, jax.random.PRNGKey(5))
        got, _ = eng.generate(prompts, max_new_tokens=self.N, **kw)
        want, _ = eager_loop(eng.model, eng.params, prompts, self.N,
                             self.CTX, **kw)
        np.testing.assert_array_equal(got, want)
        if kw:   # the zero embeddings the engine allocates when left out
            got, _ = eng.generate(prompts, max_new_tokens=self.N)
            zeros = {k: jnp.zeros_like(v) for k, v in kw.items()}
            want, _ = eager_loop(eng.model, eng.params, prompts, self.N,
                                 self.CTX, **zeros)
            np.testing.assert_array_equal(got, want)

    def test_topk_draws_from_the_top_k(self):
        cfg, k = tiny_cfg(), 40
        eng = ServingEngine(cfg, ServeConfig(max_context=self.CTX,
                                             batch=self.B, sampler="topk"),
                            seed=3)
        prompts = np.random.default_rng(4).integers(
            0, cfg.vocab, (self.B, self.S)).astype(np.int32)
        n = 12
        key, other = jax.random.PRNGKey(7), jax.random.PRNGKey(8)
        a, _ = eng.generate(prompts, max_new_tokens=n, key=key)
        b, _ = eng.generate(prompts, max_new_tokens=n, key=key)
        c, _ = eng.generate(prompts, max_new_tokens=n, key=other)
        np.testing.assert_array_equal(a, b)
        assert not np.array_equal(a, c)
        for drawn in (a, c):
            fed, seen = eager_loop(eng.model, eng.params, prompts, n,
                                   self.CTX, fed=drawn)
            np.testing.assert_array_equal(fed, drawn)
            kth = np.sort(seen, axis=-1)[..., -k]            # [n, B]
            picked = np.take_along_axis(seen, drawn.T[..., None], -1)[..., 0]
            assert (picked >= kth).all()
