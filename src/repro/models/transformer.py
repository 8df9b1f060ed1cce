"""Composable model: decoder-only / encoder-decoder / cross-attn VLM.

Layers are organized as a repeating *period* of block kinds
(cfg.layer_pattern x MoE flags).  Parameters for each period position are
stacked across repetitions and applied with jax.lax.scan, keeping the HLO
O(period) in depth (critical: one CPU core compiles 48-layer models here).

Three entry modes share the block code:
  * forward()      — full sequence, no cache (train / scoring)
  * prefill()      — full sequence, builds the serving cache
  * decode_step()  — one token per sequence against the cache
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import module as nn
from repro.models.config import ArchConfig

ATTN_KINDS = ("global", "local", "chunk", "cross")


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _init_block(key: nn.KeyGen, cfg: ArchConfig, kind: str, is_moe: bool):
    c = nn.ParamCollector()
    c.add("ln1", L.init_norm(cfg))
    if kind in ("global", "local", "chunk"):
        c.add("mixer", L.init_attention(key, cfg))
    elif kind == "cross":
        c.add("mixer", L.init_attention(key, cfg, cross=True))
        c.add("xgate", nn.zeros((), ()))
    elif kind == "rglru":
        c.add("mixer", L.init_rglru(key, cfg))
    elif kind == "ssd":
        c.add("mixer", L.init_mamba2(key, cfg))
    else:
        raise ValueError(kind)
    if cfg.encoder_layers and kind in ATTN_KINDS:
        # encoder-decoder blocks: self-attn + cross-attn + FFN
        c.add("xattn", L.init_attention(key, cfg, cross=True))
        c.add("lnx", L.init_norm(cfg))
    if cfg.d_ff > 0:
        c.add("ln2", L.init_norm(cfg))
        c.add("ffn", L.init_moe(key, cfg) if is_moe else L.init_mlp(key, cfg))
    return c.params, c.axes


def _init_encoder_block(key: nn.KeyGen, cfg: ArchConfig):
    c = nn.ParamCollector()
    c.add("ln1", L.init_norm(cfg))
    c.add("mixer", L.init_attention(key, cfg))
    c.add("ln2", L.init_norm(cfg))
    c.add("ffn", L.init_mlp(key, cfg))
    return c.params, c.axes


def init_model(key_or_seed, cfg: ArchConfig):
    cfg.validate()
    key = nn.KeyGen(key_or_seed)
    c = nn.ParamCollector()
    c.add("embed", nn.embed(key(), cfg.vocab, cfg.d_model))
    if cfg.frontend:
        c.add("frontend_proj",
              nn.dense(key(), cfg.frontend_dim, cfg.d_model,
                       ("frontend", "embed")))
    kinds = cfg.layer_kinds()
    period, reps = cfg.period, cfg.n_layers // cfg.period
    blocks_p, blocks_a = {}, {}
    for j in range(period):
        per_rep = [
            _init_block(key, cfg, kinds[j], cfg.is_moe_layer(j))
            for _ in range(reps)
        ]
        blocks_p[f"pos{j}"] = nn.stack_params([p for p, _ in per_rep])
        blocks_a[f"pos{j}"] = nn.stack_axes(per_rep[0][1])
    c.params["blocks"] = blocks_p
    c.axes["blocks"] = blocks_a
    tail_p, tail_a = [], []
    for i in range(reps * period, cfg.n_layers):
        p, a = _init_block(key, cfg, kinds[i], cfg.is_moe_layer(i))
        tail_p.append(p)
        tail_a.append(a)
    c.params["tail"] = tail_p
    c.axes["tail"] = tail_a
    if cfg.encoder_layers:
        enc = [_init_encoder_block(key, cfg)
               for _ in range(cfg.encoder_layers)]
        c.params["encoder"] = nn.stack_params([p for p, _ in enc])
        c.axes["encoder"] = nn.stack_axes(enc[0][1])
        c.add("enc_norm", L.init_norm(cfg))
    c.add("final_norm", L.init_norm(cfg))
    if not cfg.tie_embeddings:
        c.add("lm_head", nn.dense(key(), cfg.d_model, cfg.vocab,
                                  ("embed", "vocab")))
    return c.params, c.axes


def init_model_params_only(seed, cfg: ArchConfig, dtype=jnp.bfloat16):
    """Params cast to `dtype` (axes discarded) — eval_shape friendly.

    Built as one compiled program, so each float32 draw is cast as it is
    made: the float32 tree never lives on the device whole (at gemma3-12b
    width it alone would take most of a 16 GB chip)."""
    return jax.jit(lambda: nn.tree_cast(init_model(seed, cfg)[0], dtype))()


def init_model_axes(cfg: ArchConfig):
    """Logical-axes twin tree, built without allocating any array."""
    box = {}

    def f():
        p, a = init_model(0, cfg)
        box["axes"] = a
        return p

    jax.eval_shape(f)
    return box["axes"]


# ---------------------------------------------------------------------------
# Block application (shared by train / prefill / decode)
# ---------------------------------------------------------------------------


def _apply_block(bp, x, cfg: ArchConfig, kind: str, is_moe: bool, *,
                 positions, frontend_kv=None, mode: str = "train",
                 cache=None, lengths=None, kv_chunk: int = 512,
                 decode_attn_fn=None, decode_update_fn=None):
    """Returns (x, new_cache, aux)."""
    new_cache = cache
    aux = 0.0
    h = L.norm(bp["ln1"], x, cfg)
    if kind in ("global", "local", "chunk"):
        if mode == "decode":
            y, new_cache = _decode_self_attention(
                bp["mixer"], h, cfg, kind, cache, lengths,
                decode_attn_fn=decode_attn_fn,
                decode_update_fn=decode_update_fn)
        else:
            y = L.attention_block(bp["mixer"], h, cfg, kind,
                                  positions=positions, kv_chunk=kv_chunk)
            if mode == "prefill":
                new_cache = _build_attn_cache(bp["mixer"], h, cfg, kind,
                                              cache, positions)
    elif kind == "cross":
        if mode == "decode":
            q, _, _ = L.attention_qkv(bp["mixer"], h, cfg, kv_src=h[:, :0])
            from repro.kernels.decode_attention import ref as da_ref
            o = da_ref.decode_attention(
                q[:, 0], cache["k"], cache["v"],
                jnp.full((x.shape[0],), cache["k"].shape[1], jnp.int32))
            y = L.attention_out(bp["mixer"], o[:, None], cfg)
        else:
            y = L.attention_block(bp["mixer"], h, cfg, "cross",
                                  positions=positions,
                                  frontend_kv=frontend_kv, kv_chunk=kv_chunk)
            if mode == "prefill":
                _, ck, cv = L.attention_qkv(bp["mixer"], h, cfg,
                                            kv_src=frontend_kv)
                new_cache = {"k": ck, "v": cv}
        y = jnp.tanh(bp["xgate"]).astype(y.dtype) * y
    elif kind == "rglru":
        state = None if mode == "train" else \
            ((cache["conv"], cache["h"]) if mode == "decode" else None)
        y, st = L.rglru_block(bp["mixer"], h, cfg, state)
        if mode != "train":
            new_cache = {"conv": st[0], "h": st[1]}
    elif kind == "ssd":
        state = None if mode == "train" else \
            ((cache["conv"], cache["state"]) if mode == "decode" else None)
        y, st = L.mamba2_block(bp["mixer"], h, cfg, state)
        if mode != "train":
            new_cache = {"conv": st[0], "state": st[1]}
    else:
        raise ValueError(kind)
    x = x + y

    if cfg.encoder_layers and kind in ATTN_KINDS and "xattn" in bp:
        hx = L.norm(bp["lnx"], x, cfg)
        if mode == "decode":
            q, _, _ = L.attention_qkv(bp["xattn"], hx, cfg)
            from repro.kernels.decode_attention import ref as da_ref
            o = da_ref.decode_attention(
                q[:, 0], cache["xk"], cache["xv"],
                jnp.full((x.shape[0],), cache["xk"].shape[1], jnp.int32))
            y = L.attention_out(bp["xattn"], o[:, None], cfg)
            # the encoder memory is static during decode: carry it through
            new_cache = dict(new_cache or {})
            new_cache.update({"xk": cache["xk"], "xv": cache["xv"]})
        else:
            y = L.attention_block(bp["xattn"], hx, cfg, "cross",
                                  positions=positions,
                                  frontend_kv=frontend_kv, kv_chunk=kv_chunk)
            if mode == "prefill":
                _, ck, cv = L.attention_qkv(bp["xattn"], hx, cfg,
                                            kv_src=frontend_kv)
                new_cache = dict(new_cache or {})
                new_cache.update({"xk": ck, "xv": cv})
        x = x + y

    if cfg.d_ff > 0:
        h2 = L.norm(bp["ln2"], x, cfg)
        if is_moe:
            y2, probs = L.moe_block(bp["ffn"], h2, cfg,
                                    dropless=(mode != "train"))
            aux = L.moe_aux_loss(probs)
        else:
            y2 = L.mlp_block(bp["ffn"], h2, cfg)
        x = x + y2
    return x, new_cache, aux


# --- attention cache helpers -------------------------------------------------


def _cache_window(cfg: ArchConfig, kind: str, max_len: int) -> int:
    if kind == "local":
        return min(cfg.window, max_len)
    if kind == "chunk":
        return min(cfg.window, max_len)
    return max_len


def _build_attn_cache(p, h, cfg: ArchConfig, kind: str, cache, positions):
    """Write prefilled K/V into the (possibly rolling) cache buffer."""
    _, k, v = L.attention_qkv(p, h, cfg)
    k = L.rope(k, positions, theta=cfg.rope_theta, fraction=cfg.rope_fraction)
    B, S = k.shape[0], k.shape[1]
    W = cache["k"].shape[1]
    if kind in ("local", "chunk") and S > W:
        k, v = k[:, -W:], v[:, -W:]
        pos = positions[..., -W:]
    else:
        pos = positions[..., :S]
    slots = (pos % W).astype(jnp.int32)
    slots = jnp.broadcast_to(slots, (B, k.shape[1]))
    bidx = jnp.arange(B)[:, None]
    ck = cache["k"].at[bidx, slots].set(k.astype(cache["k"].dtype))
    cv = cache["v"].at[bidx, slots].set(v.astype(cache["v"].dtype))
    return {"k": ck, "v": cv}


def _decode_self_attention(p, h, cfg: ArchConfig, kind: str, cache, lengths,
                           *, decode_attn_fn=None, decode_update_fn=None):
    """One-token attention against the cache; writes the new K/V first."""
    from repro.kernels.decode_attention import ref as da_ref
    B = h.shape[0]
    pos = lengths[:, None]                                  # [B, 1]
    q, k, v = L.attention_qkv(p, h, cfg)
    q = L.rope(q, pos, theta=cfg.rope_theta, fraction=cfg.rope_fraction)
    k = L.rope(k, pos, theta=cfg.rope_theta, fraction=cfg.rope_fraction)
    W = cache["k"].shape[1]
    slot = (lengths % W).astype(jnp.int32)
    if decode_update_fn is not None:
        # seq-sharded cache: only the owning shard writes (no resharding)
        ck, cv = decode_update_fn(cache["k"], cache["v"], k[:, 0], v[:, 0],
                                  slot)
    else:
        bidx = jnp.arange(B)
        ck = cache["k"].at[bidx, slot].set(k[:, 0].astype(cache["k"].dtype))
        cv = cache["v"].at[bidx, slot].set(v[:, 0].astype(cache["v"].dtype))
    if kind == "chunk":
        valid = (lengths % cfg.window) + 1
        window = 0
    elif kind == "local":
        valid = jnp.minimum(lengths + 1, W)
        window = 0
    else:
        valid = lengths + 1
        window = 0
    attn = decode_attn_fn or (lambda q_, k_, v_, l_, **kw:
                              da_ref.decode_attention(q_, k_, v_, l_, **kw))
    o = attn(q[:, 0], ck, cv, valid.astype(jnp.int32), window=window)
    y = L.attention_out(p, o[:, None], cfg)
    return y, {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# Whole-model passes
# ---------------------------------------------------------------------------


def _embed_tokens(params, cfg: ArchConfig, tokens):
    x = params["embed"][tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return x.astype(jnp.dtype(cfg.dtype))


def _frontend_kv(params, cfg: ArchConfig, frontend_emb):
    if frontend_emb is None:
        return None
    return jnp.einsum("bfe,ed->bfd", frontend_emb.astype(jnp.float32),
                      params["frontend_proj"].astype(jnp.float32)
                      ).astype(jnp.dtype(cfg.dtype))


def _encode(params, cfg: ArchConfig, frontend_kv, kv_chunk: int = 512):
    """Bidirectional encoder over frontend embeddings (audio)."""
    x = frontend_kv

    def body(x, bp):
        h = L.norm(bp["ln1"], x, cfg)
        y = L.attention_block(bp["mixer"], h, cfg, "encoder",
                              positions=jnp.arange(x.shape[1])[None, :],
                              kv_chunk=kv_chunk)
        x = x + y
        h = L.norm(bp["ln2"], x, cfg)
        return x + L.mlp_block(bp["ffn"], h, cfg), None

    x, _ = jax.lax.scan(body, x, params["encoder"])
    return L.norm(params["enc_norm"], x, cfg)


def _unembed(params, cfg: ArchConfig, x):
    x = L.norm(params["final_norm"], x, cfg)
    if cfg.tie_embeddings:
        # T5-style 1/sqrt(d) logit scaling: the tied embedding matrix has
        # unit-variance rows, so unscaled tied logits start with std
        # ~sqrt(d) and CE ~ d/2 — poison for early training.
        w = params["embed"].astype(x.dtype)
        return jnp.einsum("bse,ve->bsv", x, w) * (cfg.d_model ** -0.5)
    return jnp.einsum("bse,ev->bsv", x, params["lm_head"].astype(x.dtype))


def forward(params, cfg: ArchConfig, tokens, frontend_emb=None, *,
            remat: bool = False, kv_chunk: int = 512, unroll: int = 1):
    """Full-sequence forward -> (logits [B,S,V], aux_loss)."""
    x = _embed_tokens(params, cfg, tokens)
    positions = jnp.arange(tokens.shape[1])[None, :]
    fkv = _frontend_kv(params, cfg, frontend_emb)
    if cfg.encoder_layers:
        fkv = _encode(params, cfg, fkv, kv_chunk)
    kinds = cfg.layer_kinds()
    period, reps = cfg.period, cfg.n_layers // cfg.period

    def body(carry, rep_params):
        x, aux = carry
        for j in range(period):
            x, _, a = _apply_block(rep_params[f"pos{j}"], x, cfg, kinds[j],
                                   cfg.is_moe_layer(j), positions=positions,
                                   frontend_kv=fkv, mode="train",
                                   kv_chunk=kv_chunk)
            aux = aux + a
        return (x, aux), None

    body_fn = jax.checkpoint(body) if remat else body
    (x, aux), _ = jax.lax.scan(body_fn, (x, jnp.zeros((), jnp.float32)),
                               params["blocks"], unroll=unroll)
    for i, bp in enumerate(params["tail"]):
        li = reps * period + i
        x, _, a = _apply_block(bp, x, cfg, kinds[li], cfg.is_moe_layer(li),
                               positions=positions, frontend_kv=fkv,
                               mode="train", kv_chunk=kv_chunk)
        aux = aux + a
    return _unembed(params, cfg, x), aux


# ---------------------------------------------------------------------------
# Serving: cache init / prefill / decode
# ---------------------------------------------------------------------------


def init_cache(cfg: ArchConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> dict:
    """Stacked per-period-position cache pytree."""
    kinds = cfg.layer_kinds()
    period, reps = cfg.period, cfg.n_layers // cfg.period
    KvH, Dh = cfg.n_kv_heads, cfg.head_dim_

    def one(kind, n):
        if kind in ("global", "local", "chunk"):
            W = _cache_window(cfg, kind, max_len)
            c = {"k": jnp.zeros((n, batch, W, KvH, Dh), dtype),
                 "v": jnp.zeros((n, batch, W, KvH, Dh), dtype)}
        elif kind == "cross":
            c = {"k": jnp.zeros((n, batch, cfg.frontend_len, KvH, Dh), dtype),
                 "v": jnp.zeros((n, batch, cfg.frontend_len, KvH, Dh), dtype)}
        elif kind == "rglru":
            W = cfg.lru_width or cfg.d_model
            c = {"conv": jnp.zeros((n, batch, 3, W), dtype),
                 "h": jnp.zeros((n, batch, W), jnp.float32)}
        elif kind == "ssd":
            Din, H, G, N = L.mamba2_split(cfg)
            P = cfg.ssm_head_dim
            c = {"conv": jnp.zeros((n, batch, cfg.conv_kernel - 1,
                                    Din + 2 * G * N), dtype),
                 "state": jnp.zeros((n, batch, H, P, N), jnp.float32)}
        else:
            raise ValueError(kind)
        if cfg.encoder_layers and kind in ATTN_KINDS:
            c["xk"] = jnp.zeros((n, batch, cfg.frontend_len, KvH, Dh), dtype)
            c["xv"] = jnp.zeros((n, batch, cfg.frontend_len, KvH, Dh), dtype)
        return c

    cache = {"blocks": {f"pos{j}": one(kinds[j], reps)
                        for j in range(period)},
             "tail": [jax.tree.map(lambda y: y[0], one(kinds[i], 1))
                      for i in range(reps * period, cfg.n_layers)]}
    return cache


def prefill(params, cfg: ArchConfig, tokens, cache, frontend_emb=None, *,
            kv_chunk: int = 512, unroll: int = 1):
    """Equal-length batched prefill: runs the full sequence, fills the cache.
    Returns (last-token logits [B,V], cache, lengths [B])."""
    B, S = tokens.shape
    x = _embed_tokens(params, cfg, tokens)
    positions = jnp.arange(S)[None, :]
    fkv = _frontend_kv(params, cfg, frontend_emb)
    if cfg.encoder_layers:
        fkv = _encode(params, cfg, fkv, kv_chunk)
    kinds = cfg.layer_kinds()
    period, reps = cfg.period, cfg.n_layers // cfg.period

    def body(x, inp):
        rep_params, rep_cache = inp
        new_rep_cache = {}
        for j in range(period):
            x, nc, _ = _apply_block(rep_params[f"pos{j}"], x, cfg, kinds[j],
                                    cfg.is_moe_layer(j), positions=positions,
                                    frontend_kv=fkv, mode="prefill",
                                    cache=rep_cache[f"pos{j}"],
                                    kv_chunk=kv_chunk)
            new_rep_cache[f"pos{j}"] = nc
        return x, new_rep_cache

    x, new_blocks = jax.lax.scan(body, x,
                                 (params["blocks"], cache["blocks"]),
                                 unroll=unroll)
    new_tail = []
    for i, bp in enumerate(params["tail"]):
        li = reps * period + i
        x, nc, _ = _apply_block(bp, x, cfg, kinds[li], cfg.is_moe_layer(li),
                                positions=positions, frontend_kv=fkv,
                                mode="prefill", cache=cache["tail"][i],
                                kv_chunk=kv_chunk)
        new_tail.append(nc)
    logits = _unembed(params, cfg, x[:, -1:])[:, 0]
    lengths = jnp.full((B,), S, jnp.int32)
    return logits, {"blocks": new_blocks, "tail": new_tail}, lengths


def decode_step(params, cfg: ArchConfig, tokens, lengths, cache, *,
                decode_attn_fn=None, decode_update_fn=None,
                unroll: int = 1):
    """One decode step.  tokens [B, 1]; lengths [B] = current cache length.
    Returns (logits [B, V], new_cache)."""
    x = _embed_tokens(params, cfg, tokens)
    positions = lengths[:, None]
    kinds = cfg.layer_kinds()
    period, reps = cfg.period, cfg.n_layers // cfg.period

    def body(x, inp):
        rep_params, rep_cache = inp
        new_rep_cache = {}
        for j in range(period):
            x, nc, _ = _apply_block(rep_params[f"pos{j}"], x, cfg, kinds[j],
                                    cfg.is_moe_layer(j), positions=positions,
                                    mode="decode", cache=rep_cache[f"pos{j}"],
                                    lengths=lengths,
                                    decode_attn_fn=decode_attn_fn,
                                    decode_update_fn=decode_update_fn)
            new_rep_cache[f"pos{j}"] = nc
        return x, new_rep_cache

    x, new_blocks = jax.lax.scan(body, x,
                                 (params["blocks"], cache["blocks"]),
                                 unroll=unroll)
    new_tail = []
    for i, bp in enumerate(params["tail"]):
        li = reps * period + i
        x, nc, _ = _apply_block(bp, x, cfg, kinds[li], cfg.is_moe_layer(li),
                                positions=positions, mode="decode",
                                cache=cache["tail"][i], lengths=lengths,
                                decode_attn_fn=decode_attn_fn,
                                decode_update_fn=decode_update_fn)
        new_tail.append(nc)
    logits = _unembed(params, cfg, x)[:, 0]
    return logits, {"blocks": new_blocks, "tail": new_tail}
