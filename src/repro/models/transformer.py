"""Decoder-only transformer: dense, MoE (interleaved, EP) and VLM variants,
with full and sliding-window layers mixed where the config says so.

Layers are scan-stacked (params carry a leading L dim) so the lowered HLO is
one rolled loop — essential to keep 80 dry-run compiles cheap — and the layer
body is rematerialized (``jax.checkpoint``) for training memory.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.parallel.sharding import shard
from .config import ModelConfig
from . import layers as L


def _dtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def _layer_init(key, cfg: ModelConfig, moe: bool, dtype):
    k1, k2 = jax.random.split(key)
    p = {"ln1": jnp.ones((cfg.d_model,), dtype),
         "ln2": jnp.ones((cfg.d_model,), dtype),
         "attn": L.attn_init(k1, cfg, dtype)}
    if moe:
        p["moe"] = L.moe_init(k2, cfg, dtype)
    else:
        p["mlp"] = L.mlp_init(k2, cfg, dtype=dtype)
    return p


def init_params(cfg: ModelConfig, key) -> Dict:
    dtype = _dtype(cfg)
    kE, kL, kH = jax.random.split(key, 3)
    n_groups = cfg.num_layers // cfg.moe_interleave if cfg.moe_experts \
        else cfg.num_layers
    per = cfg.moe_interleave if cfg.moe_experts else 1

    def group_init(gkey):
        ks = jax.random.split(gkey, per)
        group = {}
        for i in range(per):
            moe = cfg.moe_experts > 0 and (i == per - 1)
            group[f"l{i}"] = _layer_init(ks[i], cfg, moe, dtype)
        return group

    gkeys = jax.random.split(kL, n_groups)
    stacked = jax.vmap(group_init)(gkeys)
    params = {
        "embed": L.embed_init(kE, cfg.vocab_size, cfg.d_model, dtype),
        "final_norm": jnp.ones((cfg.d_model,), dtype),
        "layers": stacked,
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.embed_init(kH, cfg.vocab_size, cfg.d_model,
                                         dtype)
    return params


def _group_meta(cfg: ModelConfig):
    """``layers.layer_meta`` in scan-group layout (G, per, ...), or None."""
    meta = L.layer_meta(cfg)
    if meta is None:
        return None
    per = cfg.moe_interleave if cfg.moe_experts else 1
    return {k: v.reshape((cfg.num_layers // per, per) + v.shape[1:])
            for k, v in meta.items()}


def _at(meta, i: int):
    return None if meta is None else {k: v[i] for k, v in meta.items()}


def _group_fwd(cfg: ModelConfig, gp: Dict, x, positions, causal: bool,
               kv_mask=None, meta=None):
    per = cfg.moe_interleave if cfg.moe_experts else 1
    kvs = []
    for i in range(per):
        lp = gp[f"l{i}"]
        h, kv = L.attn_forward(lp["attn"], cfg, L.rmsnorm(x, lp["ln1"]),
                               positions, causal=causal, return_kv=True,
                               kv_mask=kv_mask, lmeta=_at(meta, i))
        x = x + h
        kvs.append(kv)
        y = L.rmsnorm(x, lp["ln2"])
        if "moe" in lp:
            x = x + L.moe_forward(lp["moe"], cfg, y)
        else:
            x = x + L.mlp_forward(lp["mlp"], cfg, y)
        # sequence-parallel residual (keeps remat carries 1/TP-sized)
        x = shard(x, "batch", "seq", None)
    ks = jnp.stack([k for k, _ in kvs])       # (per, B, S, Hkv, hd)
    vs = jnp.stack([v for _, v in kvs])
    return x, (ks, vs)


def _embed_input(cfg: ModelConfig, params, batch) -> jax.Array:
    tok = jnp.take(params["embed"], batch["tokens"], axis=0)
    if cfg.family == "vlm" and "vision_embeds" in batch:
        vis = batch["vision_embeds"].astype(tok.dtype)   # (B, Sv, d)
        tok = jnp.concatenate([vis, tok[:, vis.shape[1]:]], axis=1)
    return tok


def _positions(cfg: ModelConfig, batch, B: int, S: int):
    if "positions" in batch:
        return batch["positions"]
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    if cfg.mrope:
        return jnp.broadcast_to(pos[None], (3, B, S))
    return pos


def _logits(cfg: ModelConfig, params, x) -> jax.Array:
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    return jnp.einsum("bsd,vd->bsv", x, head).astype(jnp.float32)


def forward(cfg: ModelConfig, params, batch, want_cache: bool = False):
    """Full-sequence forward.  Returns (logits, cache|None).

    ``batch`` may carry ``positions`` (per-row RoPE positions) and
    ``attn_mask`` (B, S) bool — False marks left-pad rows of a ragged
    serving batch, excluded as attention keys for every query."""
    x = _embed_input(cfg, params, batch)
    B, S, _ = x.shape
    x = shard(x, "batch", "seq", None)
    positions = _positions(cfg, batch, B, S)

    body = functools.partial(_group_fwd, cfg, causal=True,
                             positions=positions,
                             kv_mask=batch.get("attn_mask"))

    def scan_body(carry, inp):
        gp, meta = inp
        x, kv = body(gp, carry, meta=meta)
        return x, kv if want_cache else None

    scan_fn = jax.checkpoint(scan_body,
                             policy=jax.checkpoint_policies.nothing_saveable)
    x, kv = jax.lax.scan(scan_fn, x, (params["layers"], _group_meta(cfg)))
    x = L.rmsnorm(x, params["final_norm"])
    logits = _logits(cfg, params, x)
    cache = None
    if want_cache:
        ks, vs = kv                            # (G, per, B, S, Hkv, hd)
        Ltot = ks.shape[0] * ks.shape[1]
        cache = {"k": ks.reshape((Ltot,) + ks.shape[2:]),
                 "v": vs.reshape((Ltot,) + vs.shape[2:])}
    return logits, cache


def init_cache(cfg: ModelConfig, B: int, T: int, dtype=jnp.bfloat16):
    shape = (cfg.num_layers, B, T, cfg.num_kv_heads, cfg.hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def decode_step(cfg: ModelConfig, params, cache, tokens, pos,
                kv_start=None):
    """tokens: (B, C) int32 — C=1 is classic decode, C>1 a chunked-prefill
    step; pos: (B,) cache index of the first new token; ``kv_start``: (B,)
    first valid cache row (left-pad offset of a ragged wave batch).
    Returns (logits (B, C, V), updated cache) — see layers.attn_decode for
    the cache-frontier contract."""
    x = jnp.take(params["embed"], tokens, axis=0)         # (B, C, d)
    per = cfg.moe_interleave if cfg.moe_experts else 1
    G = cfg.num_layers // per
    ck = cache["k"].reshape((G, per) + cache["k"].shape[1:])
    cv = cache["v"].reshape((G, per) + cache["v"].shape[1:])

    def scan_body(x, inp):
        gp, ck_g, cv_g, meta = inp
        new_k, new_v = [], []
        for i in range(per):
            lp = gp[f"l{i}"]
            h, k_upd, v_upd = L.attn_decode(
                lp["attn"], cfg, L.rmsnorm(x, lp["ln1"]),
                ck_g[i], cv_g[i], pos, kv_start=kv_start,
                lmeta=_at(meta, i))
            x = x + h
            new_k.append(k_upd)
            new_v.append(v_upd)
            y = L.rmsnorm(x, lp["ln2"])
            if "moe" in lp:
                x = x + L.moe_forward(lp["moe"], cfg, y)
            else:
                x = x + L.mlp_forward(lp["mlp"], cfg, y)
        return x, (jnp.stack(new_k), jnp.stack(new_v))

    x, (nk, nv) = jax.lax.scan(scan_body, x, (params["layers"], ck, cv,
                                              _group_meta(cfg)))
    x = L.rmsnorm(x, params["final_norm"])
    logits = _logits(cfg, params, x)
    cache = {"k": nk.reshape(cache["k"].shape),
             "v": nv.reshape(cache["v"].shape)}
    return logits, cache
