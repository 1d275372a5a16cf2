"""Model FLOPs and bytes of one prefill or decode call of a dense decoder
(attention + SwiGLU MLP), worked out from the configuration's shapes alone.

FLOPs count what the algorithm needs: two per multiply-add of every matrix
product, attention over the causal pairs of the prompt (prefill) or the
valid cache positions (decode), and the logits only where the program
returns them (the last position).  Norms, rotary and softmax are left out.
Bytes are the weights read once and the key/value cache read and written.
"""
from __future__ import annotations


def _layer_matmul_params(m) -> int:
    d, hd = m.d_model, m.head_dim
    attn = d * m.n_heads * hd * 2 + d * m.n_kv_heads * hd * 2
    return attn + 3 * d * m.d_ff


def _attention_flops(m, batch: int, pairs: int) -> int:
    # scores q.k and the weighted sum p.v: 2 matmuls x 2 flops per pair
    return 4 * batch * m.n_heads * m.head_dim * pairs * m.n_layers


def weight_bytes(m, itemsize: int = 2) -> int:
    head = 0 if m.tied else m.vocab * m.d_model
    return itemsize * (m.n_layers * _layer_matmul_params(m)
                       + m.vocab * m.d_model + head)


def prefill_flops(m, batch: int, prompt: int) -> int:
    dense = 2 * batch * prompt * m.n_layers * _layer_matmul_params(m)
    logits = 2 * batch * m.d_model * m.vocab
    return dense + logits + _attention_flops(m, batch,
                                             prompt * (prompt + 1) // 2)


def decode_flops(m, batch: int, context: int) -> int:
    """One decode step whose new token attends to ``context`` positions
    (itself included)."""
    dense = 2 * batch * (m.n_layers * _layer_matmul_params(m)
                         + m.d_model * m.vocab)
    return dense + _attention_flops(m, batch, context)


def kv_bytes(m, batch: int, positions: int, itemsize: int = 2) -> int:
    return (2 * itemsize * batch * positions * m.n_kv_heads * m.head_dim
            * m.n_layers)


def prefill_bytes(m, batch: int, prompt: int) -> int:
    return weight_bytes(m) + kv_bytes(m, batch, prompt)


def decode_bytes(m, batch: int, context: int) -> int:
    return weight_bytes(m) + kv_bytes(m, batch, context)
