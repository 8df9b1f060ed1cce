"""Pallas TPU kernel: flash attention for prefill/train (causal GQA,
optional sliding-window / chunked-local masks).

Grid = (B * KvH, Sq // BQ, Sk // BK) with the KV axis innermost and
sequential: a [BQ, D] query tile stays resident in VMEM while [BK, D]
K/V tiles stream HBM->VMEM; running (m, l, acc) live in VMEM scratch.
Causal masking is block-level: fully-masked KV blocks short-circuit via
pl.when (no MXU work), the diagonal block applies the element mask.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  bq: int, bk: int, n_kv: int, window: int,
                  chunk_size: int, scale: float, causal: bool):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * bq
    k_start = ki * bk
    # block-level reachability: skip blocks fully above the causal
    # diagonal or fully outside the window/chunk
    reachable = True
    if causal:
        reachable = k_start <= q_start + bq - 1
        if window > 0:
            reachable &= k_start + bk - 1 > q_start - window
        if chunk_size > 0:
            reachable &= (k_start // chunk_size) == \
                ((q_start + bq - 1) // chunk_size)

    @pl.when(reachable)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                 # [BQ, D]
        k = k_ref[0].astype(jnp.float32)                 # [BK, D]
        v = v_ref[0].astype(jnp.float32)                 # [BK, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (bq, bk), 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (bq, bk), 1)
            ok = qpos >= kpos
            if window > 0:
                ok &= qpos - kpos < window
            if chunk_size > 0:
                ok &= (qpos // chunk_size) == (kpos // chunk_size)
            s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[:, 0]
        m_cur = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        if causal:
            p = jnp.where(ok, p, 0.0)
        l_ref[...] = jnp.broadcast_to(
            (l_ref[:, 0] * alpha + p.sum(axis=1))[:, None], l_ref.shape)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_cur[:, None], m_ref.shape)

    @pl.when(ki == n_kv - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[:, 0], 1e-30)[:, None]
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bq", "bk", "window",
                                             "chunk_size", "scale",
                                             "causal", "interpret"))
def flash_prefill_flat(q, k, v, *, bq: int = 128, bk: int = 128,
                       window: int = 0, chunk_size: int = 0,
                       scale: float = 1.0, causal: bool = True,
                       interpret: bool = True):
    """q [N, Sq, D]; k, v [N, Sk, D] with N = B * KvH * G query streams
    already matched to their KV stream -> [N, Sq, D].
    Sq % bq == 0, Sk % bk == 0, D % 128 == 0 (ops.py pads)."""
    N, Sq, D = q.shape
    Sk = k.shape[1]
    assert Sq % bq == 0 and Sk % bk == 0
    grid = (N, Sq // bq, Sk // bk)
    kernel = functools.partial(
        _flash_kernel, bq=bq, bk=bk, n_kv=Sk // bk, window=window,
        chunk_size=chunk_size, scale=scale, causal=causal)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda n, i, j: (n, i, 0)),
            pl.BlockSpec((1, bk, D), lambda n, i, j: (n, j, 0)),
            pl.BlockSpec((1, bk, D), lambda n, i, j: (n, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda n, i, j: (n, i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
