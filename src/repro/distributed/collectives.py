"""Hand-scheduled collectives for the perf pass (beyond-paper).

`seq_sharded_decode_attention`: decode attention with the KV cache
sequence dimension sharded across a mesh axis.  Each shard computes a
partial flash-softmax over its local KV slice; partials combine with one
pmax + two psums of [B, H(, D)] — instead of letting XLA's SPMD
partitioner all-gather (or "involuntarily fully rematerialize") the
multi-GB KV cache.  Used for long_500k global-attention layers
(batch = 1 leaves no batch axis to shard).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def make_seq_sharded_decode_attn(mesh: Mesh, axis: str = "data",
                                 batch_axis: str | None = None,
                                 d_axis: str | None = None):
    """Returns fn(q, k, v, lengths, *, window=0) -> [B, H, D].

    k, v [B, S, KvH, D] sharded on S over `axis` (and on B over
    `batch_axis` if given — decode_32k shards B over "data" while S rides
    "model"); q [B, H, D] and lengths [B] follow the batch sharding.

    d_axis: additionally shard head_dim over that axis (used when
    batch_axis is free, e.g. long_500k's batch=1): each shard computes a
    D-partial score contribution, psum(scores, d_axis) completes them,
    then the usual partial-softmax combine runs over `axis`.  Removes the
    d_axis-fold compute redundancy of the 1D version.
    """
    bp = batch_axis

    def local_fn(q, k, v, lengths, *, window: int):
        B, H, D_loc = q.shape
        S_loc, KvH = k.shape[1], k.shape[2]
        G = H // KvH
        full_d = D_loc * (mesh.shape[d_axis] if d_axis else 1)
        scale = full_d ** -0.5
        shard = jax.lax.axis_index(axis)
        offset = shard * S_loc

        qg = q.reshape(B, KvH, G, D_loc).astype(jnp.float32)
        s = jnp.einsum("bngd,bsnd->bngs", qg,
                       k.astype(jnp.float32)) * scale    # [B,KvH,G,S_loc]
        if d_axis:
            s = jax.lax.psum(s, d_axis)                   # complete scores
        idx = offset + jnp.arange(S_loc)
        ln = lengths[:, None]
        valid = idx[None, :] < ln
        if window > 0:
            valid &= idx[None, :] >= ln - window
        s = jnp.where(valid[:, None, None, :], s, -1e30)
        m_loc = s.max(-1)                                 # [B,KvH,G]
        p = jnp.exp(s - m_loc[..., None])
        p = jnp.where(valid[:, None, None, :], p, 0.0)
        l_loc = p.sum(-1)
        acc = jnp.einsum("bngs,bsnd->bngd", p, v.astype(jnp.float32))

        # partial-softmax combine across seq shards (acc stays D-sharded)
        m_glob = jax.lax.pmax(m_loc, axis)
        corr = jnp.exp(m_loc - m_glob)
        l = jax.lax.psum(l_loc * corr, axis)
        acc = jax.lax.psum(acc * corr[..., None], axis)
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.reshape(B, H, D_loc).astype(q.dtype)

    def fn(q, k, v, lengths, *, window: int = 0):
        f = functools.partial(local_fn, window=window)
        dsp = d_axis  # None -> replicated D
        return jax.shard_map(
            f, mesh=mesh,
            in_specs=(P(bp, None, dsp), P(bp, axis, None, dsp),
                      P(bp, axis, None, dsp), P(bp)),
            out_specs=P(bp, None, dsp),
            check_vma=False,
        )(q, k, v, lengths)

    return fn


def make_seq_sharded_cache_update(mesh: Mesh, axis: str = "data",
                                  batch_axis: str | None = None,
                                  d_axis: str | None = None):
    """Scatter one new K/V token into the seq-sharded cache without
    gathering it: only the owning shard writes."""
    bp = batch_axis

    def local_fn(cache_k, cache_v, k_new, v_new, slot):
        S_loc = cache_k.shape[1]
        shard = jax.lax.axis_index(axis)
        local_slot = slot - shard * S_loc
        in_range = (local_slot >= 0) & (local_slot < S_loc)
        idx = jnp.clip(local_slot, 0, S_loc - 1)
        B = cache_k.shape[0]
        b = jnp.arange(B)
        ck = cache_k.at[b, idx].set(
            jnp.where(in_range[:, None, None],
                      k_new.astype(cache_k.dtype), cache_k[b, idx]))
        cv = cache_v.at[b, idx].set(
            jnp.where(in_range[:, None, None],
                      v_new.astype(cache_v.dtype), cache_v[b, idx]))
        return ck, cv

    def fn(cache_k, cache_v, k_new, v_new, slot):
        dsp = d_axis
        return jax.shard_map(
            local_fn, mesh=mesh,
            in_specs=(P(bp, axis, None, dsp), P(bp, axis, None, dsp),
                      P(bp, None, dsp), P(bp, None, dsp), P(bp)),
            out_specs=(P(bp, axis, None, dsp),
                       P(bp, axis, None, dsp)),
            check_vma=False,
        )(cache_k, cache_v, k_new, v_new, slot)

    return fn
