"""Model building blocks (pure JAX, dict params, f32-stable norms).

Activation sharding constraints are injected through `repro.parallel.shard`,
which no-ops outside a mesh so the same code serves CPU smoke tests and the
512-device dry-run.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.parallel.sharding import current_rules, shard
from .config import ModelConfig

Params = Dict[str, jax.Array]


def shard_attn_q(q: jax.Array) -> jax.Array:
    """Attention activation sharding policy (DESIGN.md §5).

    Head-parallel when the head count divides the model axis (natural fit
    with column-parallel QKV — no weight gathers); otherwise sequence-
    parallel (always divisible), accepting an activation reshard instead of
    the far costlier full weight all-gather XLA would otherwise insert."""
    rules = current_rules()
    if rules is None:
        return q
    tp = rules.axis_size("model")
    H = q.shape[2]
    if tp > 1 and H % tp == 0:
        return shard(q, "batch", None, "model", None)
    return shard(q, "batch", "seq", None, None)


def sp_gather(x: jax.Array) -> jax.Array:
    """Megatron sequence parallelism, gather side: the residual stream lives
    seq-sharded over 'model' (keeps remat carries 1/TP-sized); projections
    need the full sequence, so the *activation* is all-gathered here —
    never the weights."""
    return shard(x, "batch", None, None)


def sp_scatter(x: jax.Array) -> jax.Array:
    """Sequence parallelism, scatter side: constrain a row-parallel output
    back to seq-sharded, turning the trailing all-reduce into a
    reduce-scatter."""
    return shard(x, "batch", "seq", None)


# ---------------------------------------------------------------------- #
# init helpers
# ---------------------------------------------------------------------- #
def dense_init(key, d_in: int, d_out: int, dtype) -> jax.Array:
    scale = 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale
            ).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype) -> jax.Array:
    return (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02
            ).astype(dtype)


# ---------------------------------------------------------------------- #
# norms
# ---------------------------------------------------------------------- #
def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    # statistics in f32, multiply in the input dtype: keeps backward
    # cotangents bf16 (an f32 multiply here makes XLA upcast the adjacent
    # dots' weights/activations to f32 on the wire — measured 2x collective
    # cost; see EXPERIMENTS.md §Perf iteration 3)
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    scale = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return x * scale * w.astype(x.dtype)


# ---------------------------------------------------------------------- #
# rotary embeddings (standard + M-RoPE)
# ---------------------------------------------------------------------- #
def _rope_freqs(hd: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float
               ) -> jax.Array:
    """x: (B, S, H, hd); positions: (B, S) int32."""
    freqs = _rope_freqs(x.shape[-1], theta)              # (hd/2,)
    return _rotate(x, positions[..., None].astype(jnp.float32) * freqs)


def _rotate(x: jax.Array, ang: jax.Array,
            scale: Optional[jax.Array] = None) -> jax.Array:
    """Rotate the two halves of each head of ``x`` (B, S, H, hd) by the
    angles ``ang`` (B, S, hd/2), with cos and sin times ``scale``."""
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if scale is not None:
        cos, sin = cos * scale, sin * scale
    cos = cos[:, :, None, :].astype(x.dtype)
    sin = sin[:, :, None, :].astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def yarn_inv_freq(hd: int, theta: float, factor: float, original_max: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's frequencies (Hugging Face ``_compute_yarn_parameters``):
    each frequency blends the original (extrapolated) and the ``factor``
    times slower (interpolated) one by a linear ramp over the dims whose
    wavelengths lie between ``beta_fast`` and ``beta_slow`` rotations of
    the original context."""
    pos = theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)

    def dim_of(rotations: float) -> float:
        return (hd * math.log(original_max / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_of(beta_fast)), 0)
    high = min(math.ceil(dim_of(beta_slow)), hd - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(hd // 2) - low) / (high - low), 0.0, 1.0)
    extrapolated = 1.0 - ramp
    return (1.0 / (factor * pos)) * ramp + (1.0 / pos) * extrapolated


def layer_kinds(cfg: ModelConfig) -> Tuple[str, ...]:
    """"full" or "sliding" for each layer."""
    if not cfg.layer_types:
        return ("full",) * cfg.num_layers
    kinds = tuple(cfg.layer_types[:cfg.num_layers])
    if len(kinds) < cfg.num_layers or set(kinds) - {"full", "sliding"}:
        raise ValueError(f"{cfg.name}: layer_types {cfg.layer_types} for "
                         f"{cfg.num_layers} layers of kinds full, sliding")
    return kinds


def layer_meta(cfg: ModelConfig) -> Optional[Dict[str, jax.Array]]:
    """Per-layer attention settings of a stack with mixed layer kinds,
    stacked over layers: ``window`` (the sliding window, or 2**30 on full
    layers), ``inv_freq`` (hd/2 RoPE frequencies) and ``rope_scale``
    (YaRN's attention factor on cos and sin).  None for a uniform stack,
    whose layers take the config's single RoPE and no window."""
    if not cfg.layer_types:
        return None
    hd = cfg.hd
    default = 1.0 / (cfg.rope_theta ** (np.arange(0, hd, 2,
                                                  dtype=np.float64) / hd))
    yarn = cfg.yarn_factor > 0
    full = yarn_inv_freq(hd, cfg.rope_theta, cfg.yarn_factor,
                         cfg.yarn_original_max, cfg.yarn_beta_fast,
                         cfg.yarn_beta_slow) if yarn else default
    sliding = [k == "sliding" for k in layer_kinds(cfg)]
    return {
        "window": jnp.asarray([cfg.sliding_window if s else 1 << 30
                               for s in sliding], jnp.int32),
        "inv_freq": jnp.asarray(np.stack([default if s else full
                                          for s in sliding]), jnp.float32),
        "rope_scale": jnp.asarray(
            [1.0 if s or not yarn else cfg.yarn_attention_factor
             for s in sliding], jnp.float32)}


def rope_layer(x: jax.Array, positions: jax.Array, cfg: ModelConfig,
               lmeta: Optional[Dict[str, jax.Array]]) -> jax.Array:
    """RoPE of one layer: the config's own, or the layer's table of a
    mixed stack (``layer_meta`` indexed at the layer)."""
    if lmeta is None:
        return apply_rope(x, positions, cfg.rope_theta)
    return _rotate(x, positions[..., None].astype(jnp.float32)
                   * lmeta["inv_freq"], lmeta["rope_scale"])


def apply_mrope(x: jax.Array, positions: jax.Array, theta: float,
                sections: Tuple[int, int, int]) -> jax.Array:
    """Qwen2-VL multimodal RoPE.  positions: (3, B, S) = (t, h, w) ids;
    frequency slots are split into three contiguous sections, each rotated by
    its own position stream [arXiv:2409.12191]."""
    hd = x.shape[-1]
    freqs = _rope_freqs(hd, theta)                       # (hd/2,)
    nfreq = hd // 2
    s0, s1, s2 = sections
    assert s0 + s1 + s2 == nfreq, (sections, nfreq)
    sel = jnp.concatenate([jnp.zeros(s0, jnp.int32),
                           jnp.ones(s1, jnp.int32),
                           jnp.full((s2,), 2, jnp.int32)])
    # pick per-frequency position stream: (B, S, hd/2)
    pos = jnp.take_along_axis(
        positions.transpose(1, 2, 0).astype(jnp.float32),  # (B, S, 3)
        sel[None, None, :].astype(jnp.int32) * jnp.ones(
            x.shape[:2] + (nfreq,), jnp.int32),
        axis=-1)
    return _rotate(x, pos * freqs)


# ---------------------------------------------------------------------- #
# attention core
# ---------------------------------------------------------------------- #
def _repeat_kv(k: jax.Array, group: int) -> jax.Array:
    if group == 1:
        return k
    return jnp.repeat(k, group, axis=2)


def full_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   causal: bool,
                   kv_mask: Optional[jax.Array] = None,
                   window: Optional[jax.Array] = None) -> jax.Array:
    """Direct attention.  q: (B, S, H, hd); k/v: (B, T, Hkv, hd).

    ``kv_mask``: optional (B, T) bool — False keys (e.g. left-pad rows of a
    ragged serving batch) are excluded for every query.  ``window``: a
    sliding window, keys more than ``window - 1`` positions before the
    query are excluded."""
    B, S, H, hd = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    k = _repeat_kv(k, H // Hkv)
    v = _repeat_kv(v, H // Hkv)
    scale = 1.0 / math.sqrt(hd)
    logits = jnp.einsum("bshd,bthd->bhst", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        mask = (jnp.arange(T)[None, :]
                <= jnp.arange(S)[:, None] + (T - S))
        logits = jnp.where(mask[None, None], logits, -1e30)
    if window is not None:
        near = (jnp.arange(T)[None, :]
                > jnp.arange(S)[:, None] + (T - S) - window)
        logits = jnp.where(near[None, None], logits, -1e30)
    if kv_mask is not None:
        logits = jnp.where(kv_mask[:, None, None, :], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhst,bthd->bshd", p, v)


def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      causal: bool, block: int = 1024,
                      kv_mask: Optional[jax.Array] = None,
                      window: Optional[jax.Array] = None) -> jax.Array:
    """Flash-style online-softmax attention, scanned over KV blocks.

    Peak memory is O(S * block) instead of O(S * T); this is the pure-JAX
    mirror of kernels/flash_attention.py and the path used when lowering for
    long sequences (the Pallas kernel is the TPU-native realization).
    """
    B, S, H, hd = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    if T <= block:
        return full_attention(q, k, v, causal, kv_mask=kv_mask,
                              window=window)
    group = H // Hkv
    nblk = (T + block - 1) // block
    pad = nblk * block - T
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(B, nblk, block, Hkv, hd).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nblk, block, Hkv, hd).transpose(1, 0, 2, 3, 4)
    # kv_mask is a trace-time option: the training path (None) pays no
    # extra masking work; ragged serving batches thread per-block masks
    kmb = () if kv_mask is None else (
        jnp.pad(kv_mask, ((0, 0), (0, pad)))
        .reshape(B, nblk, block).transpose(1, 0, 2),)     # (nblk, B, block)
    scale = 1.0 / math.sqrt(hd)
    qpos = jnp.arange(S)[:, None] + (T - S)

    def step(carry, inp):
        m, l, acc = carry
        kc, vc, blk, *kmc = inp
        kc = _repeat_kv(kc, group)
        vc = _repeat_kv(vc, group)
        s = jnp.einsum("bshd,bthd->bhst", q, kc,
                       preferred_element_type=jnp.float32) * scale
        kpos = blk * block + jnp.arange(block)[None, :]
        mask = kpos < T
        if causal:
            mask = mask & (kpos <= qpos)
        if window is not None:
            mask = mask & (kpos > qpos - window)
        s = jnp.where(mask[None, None], s, -1e30)
        if kmc:
            s = jnp.where(kmc[0][:, None, None, :], s, -1e30)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhst,bthd->bhsd", p.astype(vc.dtype), vc,
            preferred_element_type=jnp.float32)
        return (m_new, l, acc), None

    m0 = jnp.full((B, H, S), -1e30, jnp.float32)
    l0 = jnp.zeros((B, H, S), jnp.float32)
    a0 = jnp.zeros((B, H, S, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0), (kb, vb, jnp.arange(nblk)) + kmb)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


# ---------------------------------------------------------------------- #
# attention block (GQA + qk_norm + RoPE/M-RoPE, train/prefill/decode)
# ---------------------------------------------------------------------- #
def attn_init(key, cfg: ModelConfig, dtype) -> Params:
    d, hd = cfg.d_model, cfg.hd
    ks = jax.random.split(key, 6)
    p = {
        "wq": dense_init(ks[0], d, cfg.num_heads * hd, dtype),
        "wk": dense_init(ks[1], d, cfg.num_kv_heads * hd, dtype),
        "wv": dense_init(ks[2], d, cfg.num_kv_heads * hd, dtype),
        "wo": dense_init(ks[3], cfg.num_heads * hd, d, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def attn_qkv(p: Params, cfg: ModelConfig, x: jax.Array,
             positions: jax.Array,
             lmeta: Optional[Dict[str, jax.Array]] = None
             ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    B, S, _ = x.shape
    hd = cfg.hd
    q = (x @ p["wq"]).reshape(B, S, cfg.num_heads, hd)
    k = (x @ p["wk"]).reshape(B, S, cfg.num_kv_heads, hd)
    v = (x @ p["wv"]).reshape(B, S, cfg.num_kv_heads, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"])
        k = rmsnorm(k, p["k_norm"])
    if cfg.mrope:
        q = apply_mrope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    else:
        if positions.ndim == 3:  # mrope-shaped positions on a text model
            positions = positions[0]
        q = rope_layer(q, positions, cfg, lmeta)
        k = rope_layer(k, positions, cfg, lmeta)
    return q, k, v


def attn_forward(p: Params, cfg: ModelConfig, x: jax.Array,
                 positions: jax.Array, causal: bool = True,
                 kv: Optional[Tuple[jax.Array, jax.Array]] = None,
                 return_kv: bool = False,
                 kv_mask: Optional[jax.Array] = None,
                 lmeta: Optional[Dict[str, jax.Array]] = None):
    """Full-sequence attention.  If ``kv`` is given (cross attention), keys/
    values come from it instead of ``x``.  ``x`` may arrive seq-sharded
    (sequence-parallel residual); it is gathered here and the output is
    scattered back.  ``kv_mask`` (B, T) excludes padding keys (ragged
    serving batches); ``lmeta`` is the layer's entry of ``layer_meta``
    (its window and RoPE) in a stack of mixed layer kinds."""
    x = sp_gather(x)
    if kv is None:
        q, k, v = attn_qkv(p, cfg, x, positions, lmeta)
    else:
        B, S, _ = x.shape
        hd = cfg.hd
        q = (x @ p["wq"]).reshape(B, S, cfg.num_heads, hd)
        if cfg.qk_norm:
            q = rmsnorm(q, p["q_norm"])
        k, v = kv
    q = shard_attn_q(q)
    if return_kv:
        k = shard(k, "batch", "seq", None, None)
        v = shard(v, "batch", "seq", None, None)
    out = chunked_attention(q, k, v, causal=causal, kv_mask=kv_mask,
                            window=None if lmeta is None
                            else lmeta["window"])
    out = out.reshape(out.shape[0], out.shape[1], -1)
    out = sp_scatter(out @ p["wo"])
    if return_kv:
        return out, (k, v)
    return out


def attn_decode(p: Params, cfg: ModelConfig, x: jax.Array,
                cache_k: jax.Array, cache_v: jax.Array, pos: jax.Array,
                kv_start: Optional[jax.Array] = None,
                lmeta: Optional[Dict[str, jax.Array]] = None):
    """Incremental attention over a slotted KV cache.

    x: (B, C, d) — C new tokens per row (C=1 is classic decode; C>1 is a
    chunked-prefill step).  cache: (B, T, Hkv, hd); pos: (B,) cache index
    the first new token is written at.  ``kv_start``: (B,) first valid
    cache row (left-pad offset of a ragged wave batch; default 0) — rows
    before it are masked out and RoPE positions are shifted so that a
    left-padded row sees exactly the geometry of an unpadded one.

    The new KV is written at cache rows [pos, pos+C); query c attends rows
    [kv_start, pos+c].  Rows past ``pos+c`` are never read, so a caller may
    leave garbage beyond its write frontier (padded prefill chunks, parked
    serving slots) as long as it overwrites row p before pos reaches p.
    On a sliding layer (``lmeta``, see ``layer_meta``) query c also skips
    the rows at or before ``pos + c - window``; the cache keeps them.
    """
    B, C = x.shape[0], x.shape[1]
    hd = cfg.hd
    if kv_start is None:
        kv_start = jnp.zeros((B,), jnp.int32)
    # sequence positions (for RoPE) exclude the left pad; cache indices keep it
    posb = (pos - kv_start)[:, None] + jnp.arange(C)[None, :]   # (B, C)
    q = (x @ p["wq"]).reshape(B, C, cfg.num_heads, hd)
    k = (x @ p["wk"]).reshape(B, C, cfg.num_kv_heads, hd)
    v = (x @ p["wv"]).reshape(B, C, cfg.num_kv_heads, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"])
        k = rmsnorm(k, p["k_norm"])
    if cfg.mrope:
        posm = jnp.broadcast_to(posb[None], (3,) + posb.shape)
        q = apply_mrope(q, posm, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, posm, cfg.rope_theta, cfg.mrope_sections)
    else:
        q = rope_layer(q, posb, cfg, lmeta)
        k = rope_layer(k, posb, cfg, lmeta)
    # write the new KV at positions [pos, pos+C) (per batch row)
    upd = jax.vmap(lambda c, s, i: jax.lax.dynamic_update_slice(
        c, s, (i, 0, 0)))
    cache_k = upd(cache_k, k, pos)
    cache_v = upd(cache_v, v, pos)
    T = cache_k.shape[1]
    # grouped-GQA einsum: never materialize the head-repeated KV (a
    # jnp.repeat here would expand the whole cache G-fold in HBM)
    G = cfg.num_heads // cfg.num_kv_heads
    qg = q.reshape(B, C, cfg.num_kv_heads, G, hd)
    scale = 1.0 / math.sqrt(hd)
    logits = jnp.einsum("bskgd,btkd->bkgst", qg, cache_k,
                        preferred_element_type=jnp.float32) * scale
    tpos = jnp.arange(T)[None, None, :]
    wpos = pos[:, None] + jnp.arange(C)[None, :]                # (B, C)
    mask = (tpos <= wpos[:, :, None]) \
        & (tpos >= kv_start[:, None, None])                     # (B, C, T)
    if lmeta is not None:
        mask = mask & (tpos > wpos[:, :, None] - lmeta["window"])
    logits = jnp.where(mask[:, None, None, :, :], logits, -1e30)
    logits = shard(logits, "batch", None, None, None, "seq")
    pr = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", pr.astype(cache_v.dtype),
                     cache_v, preferred_element_type=jnp.float32)
    out = out.astype(x.dtype).reshape(B, C, -1) @ p["wo"]
    return out, cache_k, cache_v


# ---------------------------------------------------------------------- #
# MLP variants
# ---------------------------------------------------------------------- #
def mlp_init(key, cfg: ModelConfig, d_ff: Optional[int] = None,
             dtype=jnp.bfloat16) -> Params:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.mlp == "silu_glu":
        return {"w_gate": dense_init(ks[0], d, f, dtype),
                "w_up": dense_init(ks[1], d, f, dtype),
                "w_down": dense_init(ks[2], f, d, dtype)}
    return {"w_up": dense_init(ks[0], d, f, dtype),
            "w_down": dense_init(ks[1], f, d, dtype)}


def mlp_forward(p: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    x = sp_gather(x)
    if cfg.mlp == "silu_glu":
        h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
    elif cfg.mlp == "relu2":  # Nemotron-4 squared ReLU
        h = jnp.square(jax.nn.relu(x @ p["w_up"]))
    elif cfg.mlp == "gelu":
        h = jax.nn.gelu(x @ p["w_up"])
    else:
        raise ValueError(cfg.mlp)
    h = shard(h, "batch", None, "model")
    return sp_scatter(h @ p["w_down"])


# ---------------------------------------------------------------------- #
# MoE layer: dropless on the grouped kernel; GShard capacity dispatch
# where experts shard over the model axis
# ---------------------------------------------------------------------- #
def moe_init(key, cfg: ModelConfig, dtype) -> Params:
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.moe_experts
    ks = jax.random.split(key, 5)
    scale = 1.0 / math.sqrt(d)
    p = {
        "router": dense_init(ks[0], d, e, jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (e, d, f), jnp.float32)
                   * scale).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (e, d, f), jnp.float32)
                 * scale).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (e, f, d), jnp.float32)
                   / math.sqrt(f)).astype(dtype),
    }
    if cfg.moe_shared_expert:
        p["shared"] = mlp_init(ks[4], cfg, d_ff=cfg.moe_d_ff, dtype=dtype)
    return p


def moe_forward(p: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """x: (B, S, D).  Dropless routed experts on the grouped kernel
    (:func:`moe_dropless`), except under a mesh whose 'model' axis is
    above 1, where experts shard over that axis and the GShard capacity
    dispatch (:func:`moe_capacity`) keeps the expert tensors dense."""
    rules = current_rules()
    if rules is not None and rules.axis_size("model") > 1:
        return moe_capacity(p, cfg, x)
    return moe_dropless(p, cfg, x)


def moe_route(p: Params, cfg: ModelConfig, x: jax.Array
              ) -> Tuple[jax.Array, jax.Array]:
    """Router of tokens ``x`` (T, D): (weights (T, k) f32, experts (T, k)
    int32).  Logits in float32 at HIGHEST precision, softmax over the
    experts, top-k, weights renormalised to sum to one."""
    with jax.named_scope("moe.route"):
        logits = jnp.dot(x.astype(jnp.float32),
                         p["router"].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        probs = jax.nn.softmax(logits, axis=-1)
        weights, experts = jax.lax.top_k(probs, cfg.moe_top_k)
        weights = weights / jnp.clip(weights.sum(-1, keepdims=True), 1e-9)
    return weights, experts.astype(jnp.int32)


def moe_dispatch(x: jax.Array, experts: jax.Array, num_experts: int
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The (token, expert) pairs of ``experts`` (T, k) sorted by expert:
    (order (T*k,), the pairs' indices in that order; group_sizes (E,);
    rows (T*k, D), the tokens of ``x`` (T, D) in that order)."""
    K = experts.shape[1]
    with jax.named_scope("moe.dispatch"):
        flat = experts.reshape(-1)
        order = jnp.argsort(flat, stable=True).astype(jnp.int32)
        group_sizes = jnp.bincount(flat, length=num_experts
                                   ).astype(jnp.int32)
        # in bounds by construction: clip the indices, not fill the rows
        rows = jnp.take(x, order // K, axis=0, mode="clip")
    return order, group_sizes, rows


def moe_gmm_configs(cfg: ModelConfig, rows: int, dtype_bytes: int):
    """Tuned blocks of the grouped kernel for ``rows`` routed rows: (gate
    and up, down), resolved through ``kernels.autotune`` at trace time."""
    from repro.kernels import ops
    from repro.kernels.autotune import resolve_gmm_config
    interpret = ops.interpret_default()
    D, F, E = cfg.d_model, cfg.moe_d_ff, cfg.moe_experts
    return tuple(dataclasses.replace(
        resolve_gmm_config(rows, n, k, E, dtype_bytes=dtype_bytes),
        interpret=interpret) for n, k in ((F, D), (D, F)))


def moe_dropless(p: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """Every routed (token, expert) pair is computed: route, sort the
    pairs by expert, gather their rows, grouped gate and up, SiLU-GLU,
    grouped down, unsort, and sum each token's k outputs weighted by its
    router weights (in float32)."""
    from repro.kernels import ops
    x = sp_gather(x)
    B, S, D = x.shape
    T, E, K = B * S, cfg.moe_experts, cfg.moe_top_k
    R = T * K
    xt = x.reshape(T, D)
    weights, experts = moe_route(p, cfg, xt)
    order, group_sizes, rows = moe_dispatch(xt, experts, E)
    up, down = moe_gmm_configs(cfg, R, x.dtype.itemsize)
    g = ops.gmm_op(rows, p["w_gate"], group_sizes, up)
    u = ops.gmm_op(rows, p["w_up"], group_sizes, up)
    h = (jax.nn.silu(g.astype(jnp.float32))
         * u.astype(jnp.float32)).astype(x.dtype)
    out = ops.gmm_op(h, p["w_down"], group_sizes, down)
    with jax.named_scope("moe.combine"):
        where = jnp.zeros((R,), jnp.int32).at[order].set(
            jnp.arange(R, dtype=jnp.int32))
        y = jnp.take(out, where, axis=0, mode="clip").reshape(T, K, D)
        y = jnp.einsum("tk,tkd->td", weights, y.astype(jnp.float32))
        y = y.astype(x.dtype).reshape(B, S, D)
    if cfg.moe_shared_expert:
        y = y + mlp_forward(p["shared"], cfg, x)
    return sp_scatter(y)


def moe_capacity(p: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """x: (B, S, D).  GShard capacity dispatch: each expert takes at most
    ``capacity_factor * k * S / E`` tokens of a batch row and drops the
    rest.  Tokens are grouped per batch row (G=B) so the dispatch
    tensors shard over the batch axes while experts shard over 'model'."""
    x = sp_gather(x)
    B, S, D = x.shape
    E, K = cfg.moe_experts, cfg.moe_top_k
    cap = max(1, int(cfg.capacity_factor * K * S / E))

    logits = (x.astype(jnp.float32) @ p["router"])        # (B, S, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, K)         # (B, S, K)
    gate_vals = gate_vals / jnp.clip(
        gate_vals.sum(-1, keepdims=True), 1e-9)

    # position of each (token, k) within its expert queue
    onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)  # (B, S, K, E)
    flat = onehot.reshape(B, S * K, E)
    pos_in_expert = (jnp.cumsum(flat, axis=1) - flat).reshape(B, S, K, E)
    keep = pos_in_expert < cap
    onehot = onehot * keep
    pos = jnp.einsum("bske->bsk", pos_in_expert * onehot).astype(jnp.int32)
    cap_oh = jax.nn.one_hot(pos, cap, dtype=jnp.float32)  # (B, S, K, C)

    # dispatch/combine tensors: (B, S, E, C)
    dispatch = jnp.einsum("bske,bskc->bsec", onehot, cap_oh)
    combine = jnp.einsum("bsk,bske,bskc->bsec", gate_vals, onehot, cap_oh)

    xe = jnp.einsum("bsec,bsd->ebcd", dispatch.astype(x.dtype), x)
    xe = shard(xe, "model", "batch", None, None)          # EP: experts on TP axis
    h = jax.nn.silu(jnp.einsum("ebcd,edf->ebcf", xe, p["w_gate"])) \
        * jnp.einsum("ebcd,edf->ebcf", xe, p["w_up"])
    ye = jnp.einsum("ebcf,efd->ebcd", h, p["w_down"])
    ye = shard(ye, "model", "batch", None, None)
    y = jnp.einsum("bsec,ebcd->bsd", combine.astype(x.dtype), ye)
    y = sp_scatter(y)

    if cfg.moe_shared_expert:
        y = y + mlp_forward(p["shared"], cfg, x)
    return y


# ---------------------------------------------------------------------- #
# Mamba2 (SSD) block
# ---------------------------------------------------------------------- #
def mamba_init(key, cfg: ModelConfig, dtype) -> Params:
    d, din, n = cfg.d_model, cfg.d_inner, cfg.ssm_state
    h = cfg.ssm_heads
    ks = jax.random.split(key, 4)
    proj_out = 2 * din + 2 * n + h   # [z, x, B, C, dt]
    return {
        "in_proj": dense_init(ks[0], d, proj_out, dtype),
        "conv_w": (jax.random.normal(ks[1], (cfg.ssm_conv, din + 2 * n),
                                     jnp.float32) * 0.2).astype(dtype),
        "a_log": jnp.zeros((h,), jnp.float32),
        "dt_bias": jnp.full((h,), -2.0, jnp.float32),
        "d_skip": jnp.ones((h,), jnp.float32),
        "norm_w": jnp.ones((din,), dtype),
        "out_proj": dense_init(ks[2], din, d, dtype),
    }


def _causal_conv(x: jax.Array, w: jax.Array,
                 state: Optional[jax.Array] = None):
    """Depthwise causal conv, window W.  x: (B, S, C); w: (W, C);
    state: (B, W-1, C) trailing context.  Returns (y, new_state)."""
    W = w.shape[0]
    if state is None:
        state = jnp.zeros((x.shape[0], W - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)
    y = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(W))
    new_state = xp[:, -(W - 1):] if W > 1 else state
    return jax.nn.silu(y), new_state


def _ssd_scan(xh, logdec, bmat, cmat, h0, chunk: int):
    """Chunked SSD over the sequence [arXiv:2405.21060].

    xh: (B, S, H, P); logdec: (B, S, H); bmat/cmat: (B, S, N);
    h0: (B, H, N, P).  Returns (y, h_final).  Mirrors kernels/ssd.py.
    """
    B, S, H, P = xh.shape
    N = bmat.shape[-1]
    nck = (S + chunk - 1) // chunk
    pad = nck * chunk - S
    if pad:
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        logdec = jnp.pad(logdec, ((0, 0), (0, pad), (0, 0)))
        bmat = jnp.pad(bmat, ((0, 0), (0, pad), (0, 0)))
        cmat = jnp.pad(cmat, ((0, 0), (0, pad), (0, 0)))
    L = chunk
    xc = xh.reshape(B, nck, L, H, P).transpose(1, 0, 2, 3, 4)
    ac = logdec.reshape(B, nck, L, H).transpose(1, 0, 2, 3)
    bc = bmat.reshape(B, nck, L, N).transpose(1, 0, 2, 3)
    cc = cmat.reshape(B, nck, L, N).transpose(1, 0, 2, 3)

    ii = jnp.arange(L)[:, None]
    jj = jnp.arange(L)[None, :]
    tri = jj <= ii

    def step(h, inp):
        x, a, b, c = inp                       # (B,L,H,P) (B,L,H) (B,L,N)
        acum = jnp.cumsum(a, axis=1)           # (B, L, H)
        decay = jnp.where(tri[None, :, :, None],
                          jnp.exp(acum[:, :, None, :] - acum[:, None, :, :]),
                          0.0)                 # (B, L, L, H)
        g = jnp.einsum("bin,bjn->bij", c, b)   # (B, L, L)
        y_intra = jnp.einsum("bijh,bij,bjhp->bihp",
                             decay, g, x)
        y_inter = jnp.exp(acum)[..., None] * jnp.einsum(
            "bin,bhnp->bihp", c, h)
        a_tot = acum[:, -1, :]                 # (B, H)
        bsc = jnp.exp(a_tot[:, None, :, None]
                      - acum[:, :, :, None]) * b[:, :, None, :]
        h_new = jnp.einsum("bjhn,bjhp->bhnp", bsc, x) \
            + jnp.exp(a_tot)[..., None, None] * h
        return h_new, y_intra + y_inter

    # remat the chunk body: the (B, L, L, H) decay/score tensors are cheap
    # to recompute and saving them across chunk steps for backward costs
    # nck x their size (measured 132 GB/dev on zamba2 train before this)
    step = jax.checkpoint(step)
    hT, yc = jax.lax.scan(step, h0, (xc, ac, bc, cc))
    y = yc.transpose(1, 0, 2, 3, 4).reshape(B, nck * L, H, P)
    return y[:, :S], hT


def mamba_forward(p: Params, cfg: ModelConfig, x: jax.Array,
                  state: Optional[Dict[str, jax.Array]] = None,
                  decode: bool = False):
    """Mamba2 block.  x: (B, S, d).  ``state`` carries {ssm, conv} caches for
    decoding; returns (y, new_state)."""
    B, S, d = x.shape
    din, n, h, pdim = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, \
        cfg.ssm_head_dim
    proj = x @ p["in_proj"]
    z, xin, bmat, cmat, dt = jnp.split(
        proj, [din, 2 * din, 2 * din + n, 2 * din + 2 * n], axis=-1)

    conv_in = jnp.concatenate([xin, bmat, cmat], axis=-1)
    conv_state = None if state is None else state["conv"]
    conv_out, new_conv = _causal_conv(conv_in, p["conv_w"], conv_state)
    xin, bmat, cmat = jnp.split(conv_out, [din, din + n], axis=-1)

    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + p["dt_bias"])                  # (B, S, H)
    a = -jnp.exp(p["a_log"])                              # (H,)
    logdec = dt * a                                       # (B, S, H)
    xh = xin.reshape(B, S, h, pdim).astype(jnp.float32) * dt[..., None]

    h0 = jnp.zeros((B, h, n, pdim), jnp.float32) if state is None \
        else state["ssm"]
    if decode:
        # single-step recurrence
        hs = jnp.exp(logdec[:, 0])[..., None, None] * h0 + \
            jnp.einsum("bn,bhp->bhnp", bmat[:, 0].astype(jnp.float32),
                       xh[:, 0])
        y = jnp.einsum("bn,bhnp->bhp", cmat[:, 0].astype(jnp.float32), hs)
        y = y[:, None]                                    # (B, 1, H, P)
        hT = hs
    else:
        y, hT = _ssd_scan(xh, logdec,
                          bmat.astype(jnp.float32), cmat.astype(jnp.float32),
                          h0, cfg.ssm_chunk)
    y = y + p["d_skip"][None, None, :, None] * xh
    y = y.reshape(B, S, din).astype(x.dtype)
    y = rmsnorm(y * jax.nn.silu(z), p["norm_w"])
    out = y @ p["out_proj"]
    return out, {"ssm": hT, "conv": new_conv}
