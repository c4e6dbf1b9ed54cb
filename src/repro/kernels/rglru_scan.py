"""RG-LRU gated linear recurrence Pallas kernel.

h_t = a_t ⊙ h_{t-1} + b_t over the sequence, with the hidden state carried in
VMEM scratch across sequence tiles: grid (B, W/bw, S/bs) with S innermost, so
each (batch, channel-block) streams its sequence through a resident carry —
HBM traffic is exactly one read of (a, b) and one write of h, the memory
lower bound for a linear scan.  Within a tile the recurrence runs as an
unrolled-by-XLA ``fori_loop`` over bs steps on the VPU (channels vectorize).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BW = 128
DEFAULT_BS = 256


def _rglru_kernel(a_ref, b_ref, o_ref, h_ref):
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[0]  # (bs, bw)
    b = b_ref[0]

    def body(t, h):
        h = a[t] * h + b[t]
        o_ref[0, pl.ds(t, 1), :] = h[None]
        return h

    h_ref[...] = jax.lax.fori_loop(0, a.shape[0], body, h_ref[...])


@functools.partial(jax.jit, static_argnames=("bw", "bs", "interpret"))
def rglru_scan(a: jax.Array, b: jax.Array, *, bw: int = DEFAULT_BW,
               bs: int = DEFAULT_BS, interpret: bool = False) -> jax.Array:
    """a, b: (B, S, W) f32 -> h: (B, S, W) with h_t = a_t h_{t-1} + b_t."""
    B, S, W = a.shape
    bw, bs = min(bw, W), min(bs, S)
    assert W % bw == 0 and S % bs == 0
    grid = (B, W // bw, S // bs)
    return pl.pallas_call(
        _rglru_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bs, bw), lambda bi, wi, si: (bi, si, wi)),
            pl.BlockSpec((1, bs, bw), lambda bi, wi, si: (bi, si, wi)),
        ],
        out_specs=pl.BlockSpec((1, bs, bw), lambda bi, wi, si: (bi, si, wi)),
        out_shape=jax.ShapeDtypeStruct((B, S, W), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bw,), jnp.float32)],
        interpret=interpret,
    )(a.astype(jnp.float32), b.astype(jnp.float32))
