"""Jitted public wrappers around the Pallas kernels.

These adapt model-layer shapes to kernel layouts (GQA expansion, head
flattening, block-size selection).  Kernels compile for the TPU unless the
caller passes ``interpret=True`` (the CPU tests do); a shape the kernel
cannot tile raises instead of silently taking another path.
"""
from __future__ import annotations

import jax.numpy as jnp

from .flash_attention import flash_attention
from .persistent_matmul import persistent_matmul
from .selective_scan import selective_scan

__all__ = ["pinned_matmul", "mha_flash", "mamba_scan"]

# Budget for selective_scan's double-buffered abar/bx input blocks, under
# v5e's 16 MiB default scoped VMEM (the rest holds y, c and the h state).
_SCAN_VMEM_BUDGET = 8 << 20
_SUBLANES = 8


def _pick_block(n: int, target: int) -> int:
    b = min(target, n)
    while n % b:
        b -= 1
    return max(b, 1)


def pinned_matmul(x, w, *, n_bands: int = 8, interpret: bool = False):
    """Persistent/pinned matmul with automatic block-size selection.

    ``n_bands`` is the task's virtual-SM band allocation (2·GN lanes run
    per band — Lemma 5.1's 2GN units)."""
    m, k = x.shape
    _, n = w.shape
    bm = _pick_block(m, 128)
    bn = _pick_block(n, 128)
    bk = _pick_block(k, 128)
    # the tile space must split evenly over bands x 2 lanes
    while (m // bm) * (n // bn) % (n_bands * 2) and n_bands > 1:
        n_bands //= 2
    tiles = (m // bm) * (n // bn)
    if tiles % (n_bands * 2):
        raise ValueError(
            f"{x.shape} @ {w.shape}: {tiles} tile(s) of {bm}x{bn} cannot "
            f"split over {n_bands} band(s) x 2 lanes"
        )
    return persistent_matmul(
        x, w, n_bands=n_bands, block_m=bm, block_n=bn, block_k=bk,
        interpret=interpret,
    )


def mha_flash(q, k, v, *, scale: float, window=None, interpret: bool = False):
    """q: [B, S, H, hd]; k/v: [B, S, Hkv, hd] -> [B, S, H*hd]."""
    b, s, h, hd = q.shape
    hkv = k.shape[2]
    if hkv != h:
        k = jnp.repeat(k, h // hkv, axis=2)
        v = jnp.repeat(v, h // hkv, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    qb = _pick_block(s, 256)
    out = flash_attention(
        qf, kf, vf, scale=scale, window=window, q_block=qb, kv_block=qb,
        interpret=interpret,
    )
    return out.reshape(b, h, s, hd).transpose(0, 2, 1, 3).reshape(b, s, h * hd)


def mamba_scan(abar, bx, c, *, interpret: bool = False):
    """Time chunk sized to VMEM: each step holds a [d_state, d_block] f32
    tile of abar and of bx, d_state padded to whole sublane tiles."""
    b, s, d, n = abar.shape
    d_block = _pick_block(d, 256)
    rows = -(-n // _SUBLANES) * _SUBLANES
    per_step = 2 * 2 * rows * d_block * 4   # abar + bx, double-buffered
    chunk = _pick_block(s, max(1, min(128, _SCAN_VMEM_BUDGET // per_step)))
    return selective_scan(
        abar, bx, c, chunk=chunk, d_block=d_block, interpret=interpret,
    )
