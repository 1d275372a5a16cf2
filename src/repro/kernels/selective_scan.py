"""Selective-scan (Mamba SSM) Pallas TPU kernel.

Computes  h_t = abar_t ⊙ h_{t-1} + bx_t ;  y_t = Σ_s h_t[d, s] · c_t[s]
over a sequence chunk, with the recurrent state h [d_state, d_block] held
in VMEM scratch that persists across the sequential time-chunk grid axis —
the [S, d, d_state] hidden is never materialized in HBM (the HBM-residency
of that tensor is what sinks a naive XLA lowering; see models/mamba.py).

Layout: d_state sits on the sublane axis and d on the 128-lane axis
([B, S, N, D] inside the kernel, one XLA transpose of abar and bx), so a
d_state of 16 fills two sublane tiles instead of being padded eightfold to
128 lanes — which also ran jamba-52b widths out of VMEM.

Grid: (batch, d_blocks, time_chunks); time is innermost (sequential).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["selective_scan"]


def _kernel(abar_ref, bx_ref, c_ref, y_ref, h_ref, *, chunk: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    def step(t, h):
        # h: [d_state, d_block]
        h = abar_ref[0, t] * h + bx_ref[0, t]
        y_t = (h * c_ref[0, t]).sum(axis=0, keepdims=True)  # c_t: [d_state, 1]
        y_ref[0, pl.ds(t, 1), :] = y_t.astype(y_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, chunk, step, h_ref[...])


@functools.partial(
    jax.jit, static_argnames=("chunk", "d_block", "interpret")
)
def selective_scan(
    abar: jax.Array,  # [B, S, D, N] discretized A
    bx: jax.Array,    # [B, S, D, N] discretized B·x
    c: jax.Array,     # [B, S, N]    output projection per step
    *,
    chunk: int = 128,
    d_block: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Returns y: [B, S, D] (the h-state contraction with c per step)."""
    b, s, d, n = abar.shape
    assert bx.shape == (b, s, d, n) and c.shape == (b, s, n)
    if s % chunk or d % d_block:
        raise ValueError(
            f"chunk {chunk} / d_block {d_block} must divide S={s} / D={d}"
        )
    grid = (b, d // d_block, s // chunk)
    state_major = (0, 1, 3, 2)   # [B, S, D, N] -> [B, S, N, D]

    blk = pl.BlockSpec((1, chunk, n, d_block), lambda bi, di, ti: (bi, ti, 0, di))
    return pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            blk,
            blk,
            pl.BlockSpec((1, chunk, n, 1), lambda bi, di, ti: (bi, ti, 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, chunk, d_block), lambda bi, di, ti: (bi, ti, di)
        ),
        out_shape=jax.ShapeDtypeStruct((b, s, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n, d_block), jnp.float32)],
        interpret=interpret,
    )(abar.transpose(state_major), bx.transpose(state_major), c[..., None])
