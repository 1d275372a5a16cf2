"""Persistent-grid matmul with workload pinning + self-interleaving —
the TPU-native analogue of the paper's Algorithm 1 (persistent threads).

GPU original: each persistent-thread block links many logical thread blocks
and is pinned to one SM (`%%smid` check; foreign blocks return); the kernel
is split in two halves that interleave on the same SMs ("self-interleaving",
§4.4), making the latency-inflation factor α a per-task constant.

TPU adaptation (DESIGN.md §2): there is no SM id register — pinning is *by
construction*.  The output tile space is partitioned into ``n_bands``
"virtual SM bands"; the Pallas grid is (bands, lanes=2, tiles-per-lane) and
the ``index_map`` assigns every (band, lane, step) its pinned tile so that

  * a band only ever touches its own row-band of the output (pinning),
  * the two lanes of a band interleave the band's tiles round-robin
    (self-interleaving: lane 0 takes even tiles, lane 1 odd tiles),

mirroring Algorithm 1's `[0, N/2) / [N/2, N)` split.  Giving a task a subset
of bands = assigning it 2·GN_i virtual SMs (Lemma 5.1's 2GN_i), and the
band count plugs straight into the ``t = (C-L)/m + L`` timing model.

The K dimension is accumulated in a VMEM scratch accumulator across the
innermost grid axis (TPU grids execute sequentially — "revisiting" order).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["persistent_matmul"]


def _kernel(x_ref, w_ref, o_ref, acc_ref, *, n_k: int):
    """One (band, lane, tile, k) grid step: acc += x_tile @ w_tile."""
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], w_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(ki == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("n_bands", "block_m", "block_n", "block_k", "interpret"),
)
def persistent_matmul(
    x: jax.Array,
    w: jax.Array,
    *,
    n_bands: int = 8,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """x: [M, K] @ w: [K, N] on ``n_bands`` pinned virtual-SM bands.

    Requires M % (n_bands * block_m) == 0 and N % block_n == 0,
    K % block_k == 0 (production shapes are padded upstream by ops.py).
    """
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0
    m_tiles = m // block_m
    n_tiles = n // block_n
    n_k = k // block_k
    total_tiles = m_tiles * n_tiles
    assert total_tiles % (n_bands * 2) == 0, (
        f"tile count {total_tiles} must split over {n_bands} bands x 2 lanes"
    )
    tiles_per_lane = total_tiles // (n_bands * 2)

    def tile_of(band, lane, step):
        """Pinned tile for this (virtual-SM band, interleave lane, step).

        Band b owns the contiguous tile range [b*2*T, (b+1)*2*T); its two
        lanes interleave that range round-robin (Alg. 1's two halves)."""
        linear = band * (2 * tiles_per_lane) + step * 2 + lane
        return linear // n_tiles, linear % n_tiles  # (row tile, col tile)

    grid = (n_bands, 2, tiles_per_lane, n_k)

    out = pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (block_m, block_k),
                lambda b, l, s, ki: (tile_of(b, l, s)[0], ki),
            ),
            pl.BlockSpec(
                (block_k, block_n),
                lambda b, l, s, ki: (ki, tile_of(b, l, s)[1]),
            ),
        ],
        out_specs=pl.BlockSpec(
            (block_m, block_n),
            lambda b, l, s, ki: tile_of(b, l, s),
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
    )(x, w)
    return out
