"""Chunked diagonal linear-recurrence scan: h_t = a_t * h_{t-1} + b_t.

Serves RG-LRU (recurrentgemma) and the Mamba SSM's per-channel recurrence
(falcon-mamba). The sequence axis is the innermost grid dimension so the
carry ``h`` lives in VMEM scratch across chunks; within a chunk a
fori_loop walks aligned 8-row tiles and the recurrence steps through each
tile's rows in registers — HBM traffic is exactly one read of (a, b) and
one write of h per element, the memory-bound optimum for a recurrence
(arithmetic intensity ~2 flops/6 bytes).

Shapes: a, b: [B, S, D]; h0: [B, D] -> out h: [B, S, D].
Blocks: (1, chunk, D) with chunk a multiple of 8, and h0 as (1, 1, D) —
every block's last two dims are tile-aligned or whole.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["lru_scan"]

_SUBLANES = 8


def _lru_kernel(h0_ref, a_ref, b_ref, o_ref, h_scr, *, chunk):
    si = pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(jnp.float32)

    d = h_scr.shape[-1]
    row = jax.lax.broadcasted_iota(jnp.int32, (_SUBLANES, d), 0)

    def block(k, h):
        # One 8-row tile per iteration: aligned loads and one aligned
        # store; the recurrence itself steps through the tile's rows.
        start = pl.multiple_of(k * _SUBLANES, _SUBLANES)
        a = a_ref[0, pl.ds(start, _SUBLANES), :].astype(jnp.float32)
        b = b_ref[0, pl.ds(start, _SUBLANES), :].astype(jnp.float32)
        out = jnp.zeros((_SUBLANES, d), jnp.float32)
        for t in range(_SUBLANES):
            h = a[t:t + 1] * h + b[t:t + 1]
            out = jnp.where(row == t, jnp.broadcast_to(h, out.shape), out)
        o_ref[0, pl.ds(start, _SUBLANES), :] = out.astype(o_ref.dtype)
        return h

    h_scr[...] = jax.lax.fori_loop(0, chunk // _SUBLANES, block, h_scr[...])


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def lru_scan(
    a: jax.Array,   # [B, S, D] decay
    b: jax.Array,   # [B, S, D] input
    h0: jax.Array,  # [B, D] initial state
    *,
    chunk: int = 256,
    interpret: Optional[bool] = None,
) -> jax.Array:
    bsz, s, d = a.shape
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    # Whole 8-row tiles: the time axis is the sublane axis of each block.
    chunk = -(-min(chunk, s) // _SUBLANES) * _SUBLANES
    s_pad = -(-s // chunk) * chunk
    if s_pad != s:
        # pad with a=1, b=0 (identity recurrence) so the carry is unaffected
        a = jnp.pad(a, ((0, 0), (0, s_pad - s), (0, 0)), constant_values=1.0)
        b = jnp.pad(b, ((0, 0), (0, s_pad - s), (0, 0)))

    out = pl.pallas_call(
        functools.partial(_lru_kernel, chunk=chunk),
        grid=(bsz, s_pad // chunk),
        in_specs=[
            pl.BlockSpec((1, 1, d), lambda bi, si: (bi, 0, 0)),
            pl.BlockSpec((1, chunk, d), lambda bi, si: (bi, si, 0)),
            pl.BlockSpec((1, chunk, d), lambda bi, si: (bi, si, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, d), lambda bi, si: (bi, si, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, s_pad, d), b.dtype),
        scratch_shapes=[pltpu.VMEM((1, d), jnp.float32)],
        interpret=interpret,
    )(h0[:, None, :], a, b)
    return out[:, :s, :]
