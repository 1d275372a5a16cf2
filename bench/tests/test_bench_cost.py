"""bench/cost/dense.py against XLA's cost analysis of a one-layer model.

XLA also counts the masked half of prefill attention, norms, rotary and
softmax, so it reads a little above the model FLOPs: within 10 % here."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

import registry
from repro.configs import get_smoke_config
from repro.models import Model


@pytest.fixture(scope="module")
def one_layer():
    cfg = dataclasses.replace(get_smoke_config("qwen3-0.6b"), n_repeats=1,
                              dtype="float32")
    dims = registry.family("dense").dims({
        "model_type": "qwen3", "vocab_size": cfg.vocab,
        "hidden_size": cfg.d_model, "intermediate_size": cfg.d_ff,
        "num_hidden_layers": 1, "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
        "rope_theta": cfg.rope_theta, "rms_norm_eps": 1e-6,
        "tie_word_embeddings": True})
    model = Model(cfg)
    return dims, model, jax.eval_shape(model.init_params,
                                       jax.random.PRNGKey(0))


def _xla(fn, *args):
    got = jax.jit(fn).lower(*args).compile().cost_analysis()
    return got[0] if isinstance(got, list) else got


@pytest.mark.parametrize("batch,prompt", [(2, 64), (4, 128)])
def test_prefill_flops_and_bytes(one_layer, batch, prompt):
    dims, model, params = one_layer
    cost = registry.cost("dense")
    caches = jax.eval_shape(lambda: model.init_caches(batch, prompt))
    x = _xla(lambda p, t, c: model.prefill(p, t, c)[:2], params,
             jax.ShapeDtypeStruct((batch, prompt), jnp.int32), caches)
    ours = cost.prefill_flops(dims, batch, prompt)
    assert ours <= x["flops"] <= 1.10 * ours
    # the weights and the cache are read or written at least once
    assert 2 * cost.prefill_bytes(dims, batch, prompt) <= x["bytes accessed"]


@pytest.mark.parametrize("batch,context", [(2, 64), (4, 128)])
def test_decode_flops_and_bytes(one_layer, batch, context):
    """At a full cache the program attends to every position, as counted."""
    dims, model, params = one_layer
    cost = registry.cost("dense")
    caches = jax.eval_shape(lambda: model.init_caches(batch, context))
    x = _xla(model.decode_step, params,
             jax.ShapeDtypeStruct((batch, 1), jnp.int32), caches,
             jax.ShapeDtypeStruct((batch,), jnp.int32))
    ours = cost.decode_flops(dims, batch, context)
    assert ours <= x["flops"] <= 1.10 * ours
    assert 2 * cost.decode_bytes(dims, batch, context) <= x["bytes accessed"]
