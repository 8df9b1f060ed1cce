"""Pallas TPU kernel: Mamba2 SSD chunked scan (state-space duality).

TPU adaptation of the SSD algorithm (arXiv:2405.21060): instead of a
token-by-token recurrence (VPU-bound, sequential), the sequence is split
into chunks of L_C tokens.  Within a chunk the output is a masked
"attention-like" matmul (MXU work); across chunks only the [P, N] state is
carried — in VMEM scratch, while the grid walks (batch, head, chunk) with
the chunk axis innermost/sequential.

Per chunk (ca = cumulative log-decay inside the chunk):
    y_intra[i] = sum_{j<=i} exp(ca_i - ca_j) (C_i . B_j) x_j     (MXU)
    y_inter[i] = exp(ca_i) * C_i . S_prev                        (MXU)
    S_next     = exp(ca_last) S_prev + sum_j exp(ca_last - ca_j) B_j (x) x_j
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _ssd_kernel(x_ref, a_ref, b_ref, c_ref, y_ref, sfin_ref, s_ref,
                *, n_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    x = x_ref[0, :, 0].astype(jnp.float32)      # [Lc, P]
    a = a_ref[0, :, 0].astype(jnp.float32)      # [Lc]
    B = b_ref[0, :, 0].astype(jnp.float32)      # [Lc, N]
    C = c_ref[0, :, 0].astype(jnp.float32)      # [Lc, N]

    la = jnp.log(jnp.maximum(a, 1e-37))
    ca = jnp.cumsum(la)                          # [Lc] inclusive
    Lc = x.shape[0]

    # ---- intra-chunk (masked attention-like) -------------------------
    cb = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [Lc, Lc]
    ii = jax.lax.broadcasted_iota(jnp.int32, (Lc, Lc), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (Lc, Lc), 1)
    decay = jnp.exp(ca[:, None] - ca[None, :])
    scores = jnp.where(ii >= jj, cb * decay, 0.0)
    y = jax.lax.dot_general(scores, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # [Lc, P]

    # ---- inter-chunk (carried state) ----------------------------------
    s_prev = s_ref[...]                          # [P, N]
    y_inter = jax.lax.dot_general(C, s_prev, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    y = y + y_inter * jnp.exp(ca)[:, None]

    # ---- state update ---------------------------------------------------
    w = jnp.exp(ca[-1] - ca)[:, None] * B        # [Lc, N]
    s_new = s_prev * jnp.exp(ca[-1]) + jax.lax.dot_general(
        x, w, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    s_ref[...] = s_new

    y_ref[0, :, 0] = y.astype(y_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _fin():
        sfin_ref[0, 0] = s_new.astype(sfin_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_headmajor(x, a, B, C, *, chunk: int = 128,
                       interpret: bool = True):
    """x [Bsz, L, H, P]; a [Bsz, L, H]; B, C [Bsz, L, H, N] (pre-broadcast
    from G groups to H heads).  L % chunk == 0.

    Returns (y [Bsz, L, H, P], final_state [Bsz, H, P, N])."""
    Bsz, L, H, P = x.shape
    N = B.shape[3]
    assert L % chunk == 0
    n_chunks = L // chunk
    grid = (Bsz, H, n_chunks)
    kernel = functools.partial(_ssd_kernel, n_chunks=n_chunks)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, 1, P), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, h, c: (b, c, h)),
            pl.BlockSpec((1, chunk, 1, N), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, chunk, 1, N), lambda b, h, c: (b, c, h, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, P), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bsz, L, H, P), x.dtype),
            jax.ShapeDtypeStruct((Bsz, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, a, B, C)
