"""RG-LRU linear recurrence as a Pallas TPU kernel.

Hardware adaptation: a GPU implementation would block over sequence with a
chunked parallel scan across SMs.  On TPU the natural decomposition is
*channel-parallel, time-serial*: the recurrence is elementwise over the
width W, so

* grid = (B, W / BLOCK_W): each program owns a channel stripe;
* the stripe's (a, b) panels [S, BLOCK_W] are VMEM-resident (BlockSpec);
* a ``fori_loop`` walks time *in-register*: the VPU processes 8x128 lanes
  of channels per tick while the loop carries h — no HBM round-trips inside
  the scan, one store of the h panel at the end;
* the carried state enters via a third input (decode/chunk chaining) and the
  final state exits as a second output.

This keeps the MXU out (no matmuls here) but saturates VPU lanes; the
sequential dimension costs S VPU ticks per stripe, amortised across the
B x W/BLOCK_W grid — the same trade Griffin's TPU kernel makes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_W = 128


def _rglru_kernel(a_ref, b_ref, h0_ref, h_ref, hlast_ref):
    # a_ref/b_ref/h_ref: [S, BLOCK_W]; h0_ref/hlast_ref: [1, BLOCK_W]
    S = a_ref.shape[0]

    def body(t, h):
        a_t = a_ref[pl.ds(t, 1), :]
        b_t = b_ref[pl.ds(t, 1), :]
        h = a_t.astype(jnp.float32) * h + b_t.astype(jnp.float32)
        h_ref[pl.ds(t, 1), :] = h.astype(h_ref.dtype)
        return h

    h = h0_ref[...].astype(jnp.float32)
    h = jax.lax.fori_loop(0, S, body, h)
    hlast_ref[...] = h.astype(hlast_ref.dtype)


def rglru_scan_pallas(
    a: jax.Array,  # [B, S, W]
    b: jax.Array,
    h0: jax.Array | None = None,  # [B, W]
    *,
    block_w: int = BLOCK_W,
    interpret: bool = True,
):
    B, S, W = a.shape
    if W % block_w:
        raise ValueError(f"W={W} must tile by block_w={block_w}")
    if h0 is None:
        h0 = jnp.zeros((B, W), a.dtype)
    grid = (B, W // block_w)
    panel = pl.BlockSpec((None, S, block_w), lambda bi, wi: (bi, 0, wi))
    state = pl.BlockSpec((None, 1, block_w), lambda bi, wi: (bi, 0, wi))
    h, hlast = pl.pallas_call(
        _rglru_kernel,
        out_shape=(
            jax.ShapeDtypeStruct((B, S, W), a.dtype),
            jax.ShapeDtypeStruct((B, 1, W), a.dtype),
        ),
        grid=grid,
        in_specs=[panel, panel, state],
        out_specs=(panel, state),
        interpret=interpret,
    )(a, b, h0[:, None, :])
    return h, hlast[:, 0, :]
