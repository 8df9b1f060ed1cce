"""Batched serving engine: slot-managed KV cache + prefill/decode steps.

The engine is the "accelerator" of the TPU adaptation: tenants' request
streams are the flows, and the Arcus scheduler (scheduler.py) shapes what
enters each engine step.  Continuous batching: prefill one request at a
time into a free slot, decode all active slots together.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import transformer as T
from repro.models.config import ArchConfig
from repro.serving.request import Request


def _scatter_cache(batch_cache, one_cache, slot: int):
    """Write a B=1 prefill cache into batch slot `slot`.
    blocks leaves: [reps, B, ...] (batch axis 1); tail leaves: [B, ...]."""
    def blocks_leaf(cb, c1):
        return cb.at[:, slot].set(c1[:, 0].astype(cb.dtype))

    def tail_leaf(cb, c1):
        return cb.at[slot].set(c1[0].astype(cb.dtype))

    new_blocks = jax.tree.map(blocks_leaf, batch_cache["blocks"],
                              one_cache["blocks"])
    new_tail = jax.tree.map(tail_leaf, batch_cache["tail"],
                            one_cache["tail"])
    return {"blocks": new_blocks, "tail": new_tail}


#: compiled steps shared by every engine of one ArchConfig (the config is
#: a static argument), so a second engine over the same model recompiles
#: nothing
_decode = jax.jit(T.decode_step, static_argnums=(1,))
_prefill = jax.jit(T.prefill, static_argnums=(1,))


@dataclasses.dataclass
class ServingEngine:
    cfg: ArchConfig
    params: Any
    max_batch: int
    max_len: int
    greedy: bool = True

    def __post_init__(self):
        # the KV cache is kept in the model's compute dtype
        self.cache_dtype = jnp.dtype(self.cfg.dtype)
        self.cache = T.init_cache(self.cfg, self.max_batch, self.max_len,
                                  self.cache_dtype)
        self.lengths = np.zeros(self.max_batch, np.int32)
        self.active = np.zeros(self.max_batch, bool)
        self.requests: dict[int, Request] = {}

    # ------------------------------------------------------------------
    def free_slots(self) -> list[int]:
        return [i for i in range(self.max_batch) if not self.active[i]]

    def admit(self, req: Request, frontend=None) -> int:
        """Prefill one request into a free slot. Returns the slot."""
        slot = self.free_slots()[0]
        tokens = jnp.asarray(np.asarray(req.prompt, np.int32)[None, :])
        one = T.init_cache(self.cfg, 1, self.max_len, self.cache_dtype)
        logits, one, _ = _prefill(self.params, self.cfg, tokens, one, frontend)
        tok = int(jnp.argmax(logits[0]))
        self.cache = _scatter_cache(self.cache, one, slot)
        self.lengths[slot] = len(req.prompt)
        self.active[slot] = True
        req.slot = slot
        req.generated.append(tok)
        self.requests[req.req_id] = req
        # account the first generated token's cache entry on next decode
        return slot

    def step(self) -> dict[int, int]:
        """One decode step over all active slots.
        Returns {req_id: new_token}."""
        if not self.active.any():
            return {}
        last = np.zeros((self.max_batch, 1), np.int32)
        for r in self.requests.values():
            if r.slot >= 0 and r.generated:
                last[r.slot, 0] = r.generated[-1]
        logits, self.cache = _decode(
            self.params, self.cfg, jnp.asarray(last),
            jnp.asarray(self.lengths), self.cache)
        toks = np.asarray(jnp.argmax(logits, -1))
        out = {}
        for rid, r in list(self.requests.items()):
            if r.slot < 0:
                continue
            self.lengths[r.slot] += 1
            tok = int(toks[r.slot])
            r.generated.append(tok)
            out[rid] = tok
            if r.done:
                self.active[r.slot] = False
                r.slot = -1
                del self.requests[rid]
        return out

    @property
    def active_count(self) -> int:
        return int(self.active.sum())
