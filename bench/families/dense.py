"""Dense decoder family: attention (GQA, optional qk-norm) + SwiGLU MLP.

Three things live here, none of them imported from the program:

- ``dims``: the sizes of a model, read from its published ``config.json``
  keys (``bench/configs/<config>.json`` holds a copy of those keys);
- ``make_weights``: the weights, made on the device from a seed in one
  jitted call, in a layout of this module's own; ``to_program`` rearranges
  them into the parameter tree that ``repro.models.Model`` takes;
- ``forward``: the plain reference, a straightforward float32 forward pass
  at ``highest`` matmul precision, and ``quantize_fp8``, which turns the
  weights into the lower-precision control.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

# model_type -> (norm kind, qk-norm).  Qwen3 normalizes q and k per head
# with RMSNorm; OLMo uses a LayerNorm with no weight and no bias.
_KINDS = {"qwen3": ("rms", True), "olmo": ("nonparam_ln", False)}
_MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


@dataclasses.dataclass(frozen=True)
class Dims:
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int            # rows of the embedding (and of the logits)
    rope_theta: float
    norm: str             # rms | nonparam_ln
    norm_eps: float
    qk_norm: bool
    tied: bool


def dims(hf: dict) -> Dims:
    """Sizes from a published ``config.json``."""
    kind = hf["model_type"]
    if kind not in _KINDS:
        raise ValueError(f"dense family: unknown model_type {kind!r}")
    norm, qk_norm = _KINDS[kind]
    d, h = hf["hidden_size"], hf["num_attention_heads"]
    return Dims(
        d_model=d, n_layers=hf["num_hidden_layers"], n_heads=h,
        n_kv_heads=hf["num_key_value_heads"],
        head_dim=hf.get("head_dim") or d // h,
        d_ff=hf["intermediate_size"], vocab=hf["vocab_size"],
        rope_theta=float(hf["rope_theta"]), norm=norm,
        norm_eps=float(hf.get("rms_norm_eps", 1e-5)) if norm == "rms" else 1e-5,
        qk_norm=qk_norm, tied=bool(hf["tie_word_embeddings"]),
    )


def program_fields(m: Dims) -> dict:
    """What ``repro.models.ModelConfig`` must hold to run this model."""
    return {"d_model": m.d_model, "n_layers": m.n_layers,
            "n_heads": m.n_heads, "n_kv_heads": m.n_kv_heads,
            "head_dim": m.head_dim, "d_ff": m.d_ff, "vocab": m.vocab,
            "rope_theta": m.rope_theta, "norm": m.norm,
            "qk_norm": m.qk_norm, "tie_embeddings": m.tied}


# ------------------------------------------------------------------ weights

def _shapes(m: Dims) -> dict:
    d, L, hd = m.d_model, m.n_layers, m.head_dim
    s = {"embed": (m.vocab, d),
         "wq": (L, d, m.n_heads * hd), "wk": (L, d, m.n_kv_heads * hd),
         "wv": (L, d, m.n_kv_heads * hd), "wo": (L, m.n_heads * hd, d),
         "w_gate": (L, d, m.d_ff), "w_up": (L, d, m.d_ff),
         "w_down": (L, m.d_ff, d)}
    if not m.tied:
        s["lm_head"] = (m.vocab, d)
    if m.norm == "rms":
        s.update(attn_norm=(L, d), mlp_norm=(L, d), final_norm=(d,))
    if m.qk_norm:
        s.update(q_norm=(L, hd), k_norm=(L, hd))
    return s


@functools.partial(jax.jit, static_argnums=(0, 2))
def _make(m: Dims, key, dtype):
    out = {}
    for i, (name, shape) in enumerate(sorted(_shapes(m).items())):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if name in ("embed", "lm_head"):
            w = 0.02 * z
        elif name.endswith("norm"):
            w = 1.0 + 0.1 * z          # not all ones: a norm left out shows
        else:
            w = z * shape[-2] ** -0.5
        out[name] = w.astype(dtype)
    return out


def make_weights(m: Dims, key, dtype=jnp.bfloat16) -> dict:
    """All weights from ``key`` in one jitted call, on the default device."""
    return _make(m, key, jnp.dtype(dtype))


def to_program(m: Dims, w: dict) -> dict:
    """The parameter tree of ``repro.models.Model`` (pattern of one
    attention+MLP layer, stacked over the layers)."""
    L = m.n_layers

    def norm(name):
        if m.norm == "rms":
            return {"w": w[name]}
        return {"np": jnp.zeros((L,) if name != "final_norm" else (),
                                w["embed"].dtype)}

    mixer = {k: w[k] for k in ("wq", "wk", "wv", "wo")}
    if m.qk_norm:
        mixer.update(q_norm=w["q_norm"], k_norm=w["k_norm"])
    layer = {"norm1": norm("attn_norm"), "mixer": mixer,
             "norm2": norm("mlp_norm"),
             "ffn": {k: w[k] for k in ("w_gate", "w_up", "w_down")}}
    p = {"embed": {"w": w["embed"]}, "final_norm": norm("final_norm"),
         "layers": (layer,)}
    if not m.tied:
        p["lm_head"] = {"w": w["lm_head"]}
    return p


@jax.jit
def _to_fp8(w: dict) -> tuple[dict, dict]:
    q, scales = {}, {}
    for name in _MATRICES + ("embed", "lm_head"):
        if name not in w:
            continue
        x = w[name].astype(jnp.float32)
        axis = -1 if name in ("embed", "lm_head") else -2   # reduce over d_in
        scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
        scales[name] = jnp.where(scale > 0, scale, 1.0)
        q[name] = (x / scales[name]).astype(jnp.float8_e4m3fn)
    return q, scales


@jax.jit
def _from_fp8(w: dict, q: dict, scales: dict) -> dict:
    out = dict(w)
    for name in q:
        out[name] = q[name].astype(jnp.float32) * scales[name]
    return out


def quantize_fp8(w: dict) -> dict:
    """The control: every matrix rounded to float8 e4m3 with one scale per
    output row or column (the usual fp8 serving recipe); norms untouched.
    The rounding and the widening back to float32 are two programs: within
    one, XLA may fold the pair of conversions away and leave the weights
    as they were."""
    return _from_fp8(w, *_to_fp8(w))


# ---------------------------------------------------------------- reference

def _norm(m: Dims, x, weight):
    if m.norm == "rms":
        x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + m.norm_eps)
        return x * weight
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + m.norm_eps)


def _rope(x, theta):
    """Rotate-half rotary embedding over positions 0..T-1; x [B,T,H,hd]."""
    t, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / hd)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def _forward(m: Dims, w, tokens, first: int, count: int):
    b, t = tokens.shape
    hd, g = m.head_dim, m.n_heads // m.n_kv_heads
    causal = jnp.tril(jnp.ones((t, t), bool))
    ones = jnp.ones((m.n_layers, m.d_model), jnp.float32)

    def layer(x, p):
        h = _norm(m, x, p["attn_norm"])
        q = (h @ p["wq"]).reshape(b, t, m.n_heads, hd)
        k = (h @ p["wk"]).reshape(b, t, m.n_kv_heads, hd)
        v = (h @ p["wv"]).reshape(b, t, m.n_kv_heads, hd)
        if m.qk_norm:
            q = _norm(m, q, p["q_norm"])
            k = _norm(m, k, p["k_norm"])
        q, k = _rope(q, m.rope_theta), _rope(k, m.rope_theta)
        k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
        s = jnp.where(causal, s, -jnp.inf)
        a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
        x = x + a.reshape(b, t, -1) @ p["wo"]
        h = _norm(m, x, p["mlp_norm"])
        x = x + (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]
        return x, None

    names = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
    per = {k: w[k] for k in names}
    per["attn_norm"] = w.get("attn_norm", ones)
    per["mlp_norm"] = w.get("mlp_norm", ones)
    if m.qk_norm:
        per.update(q_norm=w["q_norm"], k_norm=w["k_norm"])
    x, _ = jax.lax.scan(layer, w["embed"][tokens], per)
    x = _norm(m, x[:, first:first + count],
              w.get("final_norm", jnp.ones((m.d_model,), jnp.float32)))
    head = w["embed"] if m.tied else w["lm_head"]
    return x @ head.T


def forward(m: Dims, w: dict, tokens, first: int, count: int):
    """Float32 logits [B, count, vocab] at positions first..first+count-1
    of ``tokens`` [B, T], each predicting the token after it."""
    with jax.default_matmul_precision("highest"):
        return _forward(m, w, tokens, first, count)
