"""Pallas TPU kernel: GQA decode attention (flash-style online softmax).

Serving hot-spot: one new token attends to a long KV cache (decode_32k /
long_500k shapes).  TPU adaptation of the usual GPU decode kernel:

  * grid = (B, KvH, S // S_BLOCK); the S dimension is the innermost,
    sequentially-iterated axis with running (m, l, acc) carried in VMEM
    scratch — HBM->VMEM streaming of K/V blocks, one pass, no S^2 memory.
  * the G = H/KvH query heads of one KV group form the sublane dimension of
    the MXU matmuls (padded to >= 8 sublanes by the ops wrapper), so the
    scores matmul is [G, D] x [D, S_BLOCK] — MXU-aligned when D, S_BLOCK are
    multiples of 128.
  * sliding windows mask whole blocks cheaply (block-level early-out via
    masking; positions outside [len - window, len) never contribute).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _decode_attn_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                        acc_ref, m_ref, l_ref, *, s_block: int, window: int,
                        scale: float, s_blocks: int):
    s_i = pl.program_id(2)
    length = len_ref[0]

    @pl.when(s_i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)             # [G, D]
    k = k_ref[0, :, 0].astype(jnp.float32)          # [S_BLOCK, D]
    v = v_ref[0, :, 0].astype(jnp.float32)          # [S_BLOCK, D]

    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # [G, S_BLOCK]

    idx = s_i * s_block + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    valid = idx < length
    if window > 0:
        valid = jnp.logical_and(valid, idx >= length - window)
    scores = jnp.where(valid, scores, NEG_INF)

    m_prev = m_ref[:, 0]                             # [G]
    m_cur = jnp.maximum(m_prev, scores.max(axis=1))  # [G]
    alpha = jnp.exp(m_prev - m_cur)                  # [G]
    p = jnp.exp(scores - m_cur[:, None])             # [G, S_BLOCK]
    p = jnp.where(valid, p, 0.0)
    l_cur = l_ref[:, 0] * alpha + p.sum(axis=1)
    acc = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    m_ref[...] = jnp.broadcast_to(m_cur[:, None], m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_cur[:, None], l_ref.shape)
    acc_ref[...] = acc

    @pl.when(s_i == s_blocks - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[:, 0], 1e-30)[:, None]
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("s_block", "window", "scale", "interpret"))
def decode_attention_grouped(q, k, v, lengths, *, s_block: int = 512,
                             window: int = 0, scale: float = 1.0,
                             interpret: bool = True):
    """q [B, KvH, G, D]; k, v [B, S, KvH, D]; lengths [B] -> [B, KvH, G, D].

    G must be a multiple of 8 and D a multiple of 128 (the ops wrapper
    pads); S must be a multiple of s_block."""
    B, KvH, G, D = q.shape
    S = k.shape[1]
    assert S % s_block == 0, (S, s_block)
    s_blocks = S // s_block
    grid = (B, KvH, s_blocks)
    kernel = functools.partial(_decode_attn_kernel, s_block=s_block,
                               window=window, scale=scale, s_blocks=s_blocks)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda b, n, s: (b,)),
            pl.BlockSpec((1, 1, G, D), lambda b, n, s: (b, n, 0, 0)),
            pl.BlockSpec((1, s_block, 1, D), lambda b, n, s: (b, s, n, 0)),
            pl.BlockSpec((1, s_block, 1, D), lambda b, n, s: (b, s, n, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, n, s: (b, n, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KvH, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, D), jnp.float32),
            pltpu.VMEM((G, 128), jnp.float32),
            pltpu.VMEM((G, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q, k, v)
