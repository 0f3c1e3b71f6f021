"""Flash attention (forward) as a Pallas TPU kernel.

Tiling (FlashAttention re-thought for VMEM/MXU rather than SRAM/warps):

* grid = (B*H, Sq / BLOCK_Q); each program owns one query block;
* K/V live in VMEM as whole-sequence blocks (per (b,h) slice) — on v5e,
  Skv<=4096 bf16 keys+values = 2 x 1MiB, well under the ~16MiB VMEM budget;
  the inner ``fori_loop`` walks KV in BLOCK_K chunks by ref indexing
  (``k_ref[pl.ds(...), :]``);
* online softmax: running (max, denom, acc) in f32 registers, rescaled per
  chunk — no [Sq, Skv] tensor ever exists;
* causal: the KV loop stops at the diagonal block (trip count is a
  traced-static function of the query-block index), the diagonal chunk is
  masked lane-wise; optional sliding window lower-bounds the loop start.

MXU alignment: BLOCK_Q x BLOCK_K = 128 x 128 tiles; D (head_dim) 64-256.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_Q = 128
BLOCK_K = 128
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, causal: bool, window: int,
                  block_k: int, sm_scale: float):
    # q_ref: [BLOCK_Q, D]; k_ref/v_ref: [Skv, D]; o_ref: [BLOCK_Q, D]
    qi = pl.program_id(1)
    block_q, D = q_ref.shape
    skv = k_ref.shape[0]
    q = q_ref[...].astype(jnp.float32) * sm_scale

    q_start = qi * block_q
    q_pos = q_start + jax.lax.iota(jnp.int32, block_q)[:, None]  # [bq, 1]

    # KV range touched by this query block.
    hi = skv if not causal else jnp.minimum(skv, q_start + block_q)
    num_k = pl.cdiv(hi, block_k) if causal else skv // block_k
    lo_block = 0
    if window > 0:
        lo = jnp.maximum(0, q_start - window)
        lo_block = lo // block_k

    def body(kb, state):
        m_prev, l_prev, acc = state
        k_start = kb * block_k
        kv_idx = pl.ds(k_start, block_k)
        kk = k_ref[kv_idx, :].astype(jnp.float32)
        vv = v_ref[kv_idx, :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, kk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, bk]
        k_pos = k_start + jax.lax.iota(jnp.int32, block_k)[None, :]
        mask = jnp.ones(s.shape, bool)
        if causal:
            mask &= k_pos <= q_pos
        if window > 0:
            mask &= k_pos > q_pos - window
        mask &= k_pos < skv  # guard ragged tail
        s = jnp.where(mask, s, NEG_INF)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[:, None] + jax.lax.dot_general(
            p, vv, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc

    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc0 = jnp.zeros((block_q, D), jnp.float32)
    m, l, acc = jax.lax.fori_loop(lo_block, num_k, body, (m0, l0, acc0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,  # [B, H, Sq, D]
    k: jax.Array,  # [B, H, Skv, D]
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    block_q: int = BLOCK_Q,
    block_k: int = BLOCK_K,
    interpret: bool = True,
) -> jax.Array:
    B, H, Sq, D = q.shape
    Skv = k.shape[2]
    if Sq % block_q or Skv % block_k:
        raise ValueError(
            f"Sq={Sq}/Skv={Skv} must tile by ({block_q},{block_k}); "
            "use ops.flash_attention for padding"
        )
    sm_scale = 1.0 / math.sqrt(D)
    grid = (B * H, Sq // block_q)
    kernel = functools.partial(
        _flash_kernel, causal=causal, window=window, block_k=block_k,
        sm_scale=sm_scale,
    )
    qf = q.reshape(B * H, Sq, D)
    kf = k.reshape(B * H, Skv, D)
    vf = v.reshape(B * H, Skv, D)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, D), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((None, Skv, D), lambda bh, qi: (bh, 0, 0)),
            pl.BlockSpec((None, Skv, D), lambda bh, qi: (bh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, D), lambda bh, qi: (bh, qi, 0)),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, Sq, D)
