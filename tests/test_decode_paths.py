"""The decode step's attention reads each KV head once.

``attention_decode`` splits the query into ``[B, kv, group, hd]`` against
the unexpanded ``[B, S, kv, hd]`` cache (``_sdpa_decode``) in place of
expanding K/V to every query head (``_sdpa_small``, which prefill keeps).
On the same inputs both give the same logits and the same cache; the
compiled serving decode holds no expanded K/V, and the sharded decode of
``launch/steps.py`` gathers no layer's cache across devices.
"""
import dataclasses
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import LayerSpec, Model, ModelConfig
from repro.models import attention as attn
from repro.serving import ServeConfig, ServingEngine

B, PROMPT, CACHE, STEPS = 3, 10, 24, 4
LENS = (10, 7, 3)          # each row's cache_len at the first step

# the two sum in another order (one dot per KV head against one per query
# head, f32 output against the dtype's), so outputs agree to the round-off
# of the accumulation: f32 and bf16 round-off of logits of size ~1
ATOL = {"float32": 1e-4, "bfloat16": 2e-2}


def _expanded(q, k, v, valid, scale):
    """The reference: K/V expanded to every query head, as prefill does."""
    return attn._sdpa_small(q, k, v, valid[:, None, :], scale)


def _cases():
    qwen = get_smoke_config("qwen3-0.6b")
    return {
        "gqa": qwen,
        "gqa_bf16": dataclasses.replace(qwen, dtype="bfloat16"),
        "mha": get_smoke_config("olmo-1b"),
        "window": dataclasses.replace(qwen, sliding_window=4),
        "patches": get_smoke_config("internvl2-2b"),
        "enc_dec": get_smoke_config("whisper-base"),
        "hybrid": get_smoke_config("jamba-v0.1-52b"),
    }


def _f32(x):
    return np.asarray(x, np.float32)


def _decode_both(cfg, monkeypatch):
    """Prefill once, then ``STEPS`` greedy decode steps from the same cache
    with the grouped attention and with the expanded reference.  Returns
    (cache after prefill, first cache_len, [(per-step logits, final cache)
    of the grouped run, the same of the reference run])."""
    model = Model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(1)
    toks = jax.random.randint(key, (B, PROMPT), 0, cfg.vocab)
    kw = {}
    if cfg.n_patches:
        kw["extra_embeds"] = jax.random.normal(
            key, (B, cfg.n_patches, cfg.d_model)) * 0.02
    if cfg.is_encoder_decoder:
        kw["enc_embeds"] = jax.random.normal(
            key, (B, cfg.enc_ctx, cfg.d_model)) * 0.02
    caches = model.init_caches(B, CACHE + cfg.n_patches)
    _, caches, _ = model.prefill(params, toks, caches, **kw)
    cache_len = jnp.asarray(LENS, jnp.int32) + cfg.n_patches
    runs = []
    for sdpa in (attn._sdpa_decode, _expanded):
        monkeypatch.setattr(attn, "_sdpa_decode", sdpa)
        step = jax.jit(model.decode_step)
        c, tok, logits = caches, toks[:, :1], []
        for i in range(STEPS):
            lg, c = step(params, tok, c, cache_len + i)
            logits.append(lg)
            tok = jnp.argmax(lg[:, -1], -1).astype(jnp.int32)[:, None]
        runs.append((logits, c))
    return caches, cache_len, runs


def _written(cache_len, s_max):
    """[B, S] True at the slots the steps wrote."""
    mask = np.zeros((B, s_max), bool)
    for r, n in enumerate(np.asarray(cache_len)):
        mask[r, n:n + STEPS] = True
    return mask


@pytest.mark.parametrize("case", list(_cases()))
def test_grouped_decode_matches_expanded_heads(case, monkeypatch):
    cfg = _cases()[case]
    atol = ATOL[cfg.dtype]
    before, cache_len, ((la, ca), (lb, cb)) = _decode_both(cfg, monkeypatch)
    for a, b in zip(la, lb):
        np.testing.assert_allclose(_f32(a), _f32(b), atol=atol, rtol=atol)
    for pos, spec in enumerate(cfg.pattern):
        old, new, ref = before[pos], ca[pos], cb[pos]
        assert set(new) == set(old)
        if spec.mixer == "attn":
            s_max = old["kv"].k.shape[2]
            w = _written(cache_len, s_max)
            for buf0, buf, want in zip(old["kv"], new["kv"], ref["kv"]):
                # [L, B, S, kv, hd]: every slot not written is bit-unchanged
                np.testing.assert_array_equal(
                    _f32(buf)[:, ~w], _f32(buf0)[:, ~w])
                np.testing.assert_array_equal(
                    _f32(want)[:, ~w], _f32(buf0)[:, ~w])
                np.testing.assert_allclose(_f32(buf)[:, w], _f32(want)[:, w],
                                           atol=atol, rtol=atol)
        for name in set(old) - {"kv"}:
            if name == "cross_kv":      # the step does not change it
                for buf0, buf in zip(old[name], new[name]):
                    np.testing.assert_array_equal(_f32(buf), _f32(buf0))
                continue
            for x, y in zip(jax.tree_util.tree_leaves(new[name]),
                            jax.tree_util.tree_leaves(ref[name])):
                np.testing.assert_allclose(_f32(x), _f32(y), atol=atol,
                                           rtol=atol)


# ------------------------------------------------- the compiled programs

_INSTR = re.compile(r"=\s+(\w+\[[\d,]*\])(?:\{[^}]*\})?\s+([\w\-]+)\(")


def _tiny():
    return ModelConfig(
        name="tiny", arch_type="dense", d_model=64, n_heads=4, n_kv_heads=2,
        d_ff=128, vocab=256, pattern=(LayerSpec("attn", "mlp"),),
        n_repeats=2, tie_embeddings=True, dtype="float32")


def test_decode_program_reads_each_kv_head_once():
    cfg, b, s = _tiny(), 2, 32
    eng = ServingEngine(cfg, ServeConfig(max_context=s, batch=b))
    hlo = eng._decode.lower(
        eng.params, jnp.zeros((b, 1), jnp.int32), eng.model.init_caches(b, s),
        jnp.full((b,), 12, jnp.int32)).compile().as_text()
    kv, hd, g = cfg.n_kv_heads, cfg.head_dim, cfg.n_heads // cfg.n_kv_heads
    expanded = {f"f32[{b},{s},{cfg.n_heads},{hd}]",
                f"f32[{b},{s},{kv},{g},{hd}]"}
    shapes = {m.group(1) for m in _INSTR.finditer(hlo)}
    assert f"f32[{b},{s},{kv},{hd}]" in shapes      # the layer's cache, read
    assert not shapes & expanded, shapes & expanded
    # The cache is not donated yet (PERF.md Open question 15), so the step
    # writes a fresh copy of it.  The PR that donates it turns this into
    # ``assert "input_output_alias" in hlo``.
    assert "input_output_alias" not in hlo


_SHARDED = """
import json, re
import jax
from jax.sharding import AxisType
from repro.configs import get_smoke_config
from repro.launch.steps import build_bundle
from repro.models import InputShape
from repro.models import attention as attn

def expanded(q, k, v, valid, scale):
    return attn._sdpa_small(q, k, v, valid[:, None, :], scale)

cfg = get_smoke_config("qwen3-0.6b")
mesh = jax.make_mesh((1, 8), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
out = {"kv": cfg.n_kv_heads, "h": cfg.n_heads, "hd": cfg.head_dim}
for name, sdpa in (("grouped", attn._sdpa_decode), ("expanded", expanded)):
    attn._sdpa_decode = sdpa
    b = build_bundle(cfg, InputShape("d", 64, 4, "decode"), mesh)
    with mesh:
        hlo = jax.jit(b.step_fn, in_shardings=b.in_shardings,
                      donate_argnums=b.donate_argnums).lower(
                          *b.args).compile().as_text()
    out[name] = re.findall(
        r"= \\w+\\[([\\d,]*)\\]\\S* (?:all-gather|all-reduce|all-to-all|"
        r"collective-permute|reduce-scatter)(?:-start)?\\(", hlo)
print(json.dumps(out))
"""


def test_sharded_decode_gathers_no_layer_cache():
    """``launch/steps.py``'s decode shards the cache's sequence axis over
    the model axis.  There the grouped attention moves no layer's cache
    between devices, while the expanded form gathers it whole; its
    collectives carry fewer elements in all."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep + \
        env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _SHARDED], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-4000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    kv, h, hd = got["kv"], got["h"], got["hd"]
    # [B, S, kv, hd]: one layer's K or V whole, here and expanded
    layer = {f"4,64,{kv},{hd}", f"4,64,{h},{hd}", f"4,64,{kv},{h // kv},{hd}"}

    def elements(shapes):
        return sum(int(np.prod([int(d) for d in s.split(",") if d]))
                   for s in shapes)

    assert got["grouped"] and not layer & set(got["grouped"])
    assert layer & set(got["expanded"])
    assert elements(got["grouped"]) < elements(got["expanded"])


def test_generate_twice_gives_the_same_tokens():
    eng = ServingEngine(_tiny(), ServeConfig(max_context=32, batch=2))
    prompts = np.arange(24, dtype=np.int32).reshape(2, 12) % 256
    first, _ = eng.generate(prompts, max_new_tokens=6)
    again, _ = eng.generate(prompts, max_new_tokens=6)
    np.testing.assert_array_equal(first, again)
