"""Reference for a decoder-only transformer with routed experts, mixed
full and sliding-window attention, and its weights (Mellum2's layers).

The layer equations (pre-norm)::

    h = x + Wo · attn_l(rope_l(Wq · n1(x)), rope_l(Wk · n1(x)), Wv · n1(x))
    y = h + moe(n2(h))
    moe(z) = sum over the k chosen experts e of  w_e · Wd_e · (silu(Wg_e · z) * (Wu_e · z))
    p = softmax(Wr · z) over the E experts; the k largest, w = p_top / sum(p_top)
    logits = n_f(x) · H^T                         H: the (untied) head

On a sliding layer the query at position i sees keys i - window < j <= i
and RoPE rotates at ``rope_theta``; on a full layer it sees j <= i and
RoPE is YaRN's (frequencies ``yarn_inv_freq``, cos and sin scaled by the
attention factor), as Hugging Face's ``_compute_yarn_parameters`` defines
it.  Attention is over grouped key/value heads, scaled by 1/sqrt(head_dim).

The router's product is float32 at HIGHEST in the control too: the control
lowers the precision of the products the configuration states in
bfloat16, and the program routes in float32.  Each expert is a dense
product over every token, weighted by the token's router weight for that
expert, which is zero where the token did not choose it.  One sequence at
a time, one layer at a time, queries in blocks.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .dense_transformer import QUERY_BLOCK, _norm
from .precision import HIGHEST, key_for, rounded

KINDS = {"sliding_attention": "sliding", "full_attention": "full"}


def head_dim(cfg: Dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def kinds(cfg: Dict) -> List[str]:
    """"sliding" or "full" for each of the configuration's layers."""
    return [KINDS[t] for t in cfg["layer_types"][:cfg["num_hidden_layers"]]]


def yarn_inv_freq(hd: int, rp: Dict) -> np.ndarray:
    """YaRN's inverse frequencies for the ``rope_parameters`` entry
    ``rp``: original and ``factor``-times-slower frequencies blended by a
    linear ramp over the dims between ``beta_fast`` and ``beta_slow``
    rotations of ``original_max_position_embeddings`` (truncated to whole
    dims)."""
    theta, factor = float(rp["rope_theta"]), float(rp["factor"])
    orig = float(rp["original_max_position_embeddings"])
    pos = theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)

    def dim_of(rot):
        return hd * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim_of(float(rp["beta_fast"]))), 0)
    high = min(math.ceil(dim_of(float(rp["beta_slow"]))), hd - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(hd // 2) - low) / (high - low), 0.0, 1.0)
    return (1.0 / (factor * pos)) * ramp + (1.0 / pos) * (1.0 - ramp)


def layer_rope(cfg: Dict) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Per layer: the attention window (2**30 on full layers), the RoPE
    inverse frequencies and the factor on cos and sin."""
    hd = head_dim(cfg)
    windows, freqs, scales = [], [], []
    for kind in kinds(cfg):
        rp = cfg["rope_parameters"][f"{kind}_attention"]
        if rp["rope_type"] == "yarn":
            freqs.append(yarn_inv_freq(hd, rp))
            scales.append(float(rp["attention_factor"]))
        elif rp["rope_type"] == "default":
            freqs.append(float(rp["rope_theta"])
                         ** (-np.arange(0, hd, 2, dtype=np.float64) / hd))
            scales.append(1.0)
        else:
            raise ValueError(f"unknown rope_type {rp['rope_type']!r}")
        windows.append(cfg["sliding_window"] if kind == "sliding"
                       else 1 << 30)
    return (np.asarray(windows, np.int32), np.asarray(freqs, np.float32),
            np.asarray(scales, np.float32))


def dims(cfg: Dict) -> Tuple:
    """The hashable sizes the jitted pieces are specialised on."""
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], head_dim(cfg),
            cfg["moe_intermediate_size"], cfg["num_experts"],
            cfg["num_experts_per_tok"], cfg["vocab_size"],
            cfg["num_hidden_layers"], float(cfg["rms_norm_eps"]))


# --------------------------------------------------------------------- #
# weights
# --------------------------------------------------------------------- #
def make_weights(cfg: Dict, seed: int) -> Dict[str, jax.Array]:
    """Random weights from ``seed``, bfloat16, on the device, stacked
    over layers ((in, out) matrices; experts (L, E, in, out)).  The
    layers are made one at a time inside one jitted call, so the float32
    draws of one layer are all the room it takes beside the result."""
    return _make(dims(cfg), key_for(seed))


def _layer_shapes(d) -> Dict[str, Tuple]:
    D, H, KV, hd, F, E = d[:6]
    return {"wq": (D, H * hd), "wk": (D, KV * hd), "wv": (D, KV * hd),
            "wo": (H * hd, D), "router": (D, E), "w_gate": (E, D, F),
            "w_up": (E, D, F), "w_down": (E, F, D)}


def _draw_layer(d, key) -> Dict[str, jax.Array]:
    out = {}
    for i, (name, shape) in enumerate(sorted(_layer_shapes(d).items())):
        fan_in = shape[-2]
        w = jax.random.normal(jax.random.fold_in(key, i), shape,
                              jnp.float32) / math.sqrt(fan_in)
        out[name] = w.astype(jnp.bfloat16)
    for j, name in enumerate(("ln1", "ln2")):
        g = 1.0 + 0.1 * jax.random.normal(jax.random.fold_in(key, 50 + j),
                                          (d[0],))
        out[name] = g.astype(jnp.bfloat16)
    return out


def layer_key(seed: int, li: int) -> jax.Array:
    return jax.random.fold_in(jax.random.fold_in(key_for(seed), 7), li)


@functools.partial(jax.jit, static_argnums=0)
def _make(d, key):
    D, V, L = d[0], d[7], d[8]
    layers = jax.lax.map(
        lambda li: _draw_layer(d, jax.random.fold_in(
            jax.random.fold_in(key, 7), li)), jnp.arange(L))
    k = jax.random.fold_in(key, 100)
    out = dict(layers)
    for j, name in enumerate(("embed", "head")):
        out[name] = (0.02 * jax.random.normal(jax.random.fold_in(k, j),
                                              (V, D))).astype(jnp.bfloat16)
    out["final_norm"] = (1.0 + 0.1 * jax.random.normal(
        jax.random.fold_in(k, 2), (D,))).astype(jnp.bfloat16)
    return out


@functools.partial(jax.jit, static_argnums=0)
def make_layer(d, key) -> Dict[str, jax.Array]:
    """One layer's weights, as ``make_weights`` draws layer ``li`` from
    ``layer_key(seed, li)``: each its own buffers."""
    return _draw_layer(d, key)


# --------------------------------------------------------------------- #
# the expert layer
# --------------------------------------------------------------------- #
def route(z: jax.Array, router: jax.Array, k: int
          ) -> Tuple[jax.Array, jax.Array]:
    """(weights (T, k), experts (T, k)) of tokens ``z`` (T, D) float32."""
    logits = jnp.dot(z, router.astype(jnp.float32), precision=HIGHEST)
    p = jax.nn.softmax(logits, axis=-1)
    top, experts = jax.lax.top_k(p, k)
    return top / jnp.sum(top, -1, keepdims=True), experts


def _moe(z, w, k: int, quant: Optional[str]):
    """The expert layer of tokens ``z`` (T, D) float32 with one layer's
    weights ``w``: (output (T, D) float32, experts (T, k))."""
    T, E = z.shape[0], w["router"].shape[-1]
    weights, experts = route(z, w["router"], k)
    gate = jnp.zeros((T, E), jnp.float32).at[
        jnp.arange(T)[:, None], experts].set(weights)

    def mm(a, b):
        return jnp.dot(rounded(a, quant), rounded(b, quant),
                       precision=HIGHEST)

    def expert(acc, e):
        h = jax.nn.silu(mm(z, w["w_gate"][e])) * mm(z, w["w_up"][e])
        return acc + gate[:, e][:, None] * mm(h, w["w_down"][e]), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(z), jnp.arange(E))
    return y, experts


@functools.partial(jax.jit, static_argnames=("k", "quant"))
def moe_block(x: jax.Array, w: Dict[str, jax.Array], k: int,
              quant: Optional[str] = None) -> Tuple[jax.Array, jax.Array]:
    """The expert layer of ``x`` (T, D), any dtype, computed in float32
    (``quant``: the control, its expert products' operands rounded)."""
    return _moe(x.astype(jnp.float32), w, k, quant)


# --------------------------------------------------------------------- #
# forward
# --------------------------------------------------------------------- #
def _rope(x, inv_freq, scale):
    """x: (T, heads, hd) at positions 0..T-1."""
    T, _, hd = x.shape
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = (jnp.cos(ang) * scale)[:, None, :]
    sin = (jnp.sin(ang) * scale)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=("d", "quant"))
def _layer(x, w, li, window, inv_freq, scale, d, quant):
    D, H, KV, hd, F, E, k, V, L, eps = d
    T = x.shape[0]

    def mm(a, b):
        return jnp.dot(rounded(a, quant), rounded(b, quant),
                       precision=HIGHEST)

    lw = {name: a[li] for name, a in w.items()
          if name not in ("embed", "head", "final_norm")}
    z = _norm(x, lw["ln1"], eps)
    q = _rope(mm(z, lw["wq"]).reshape(T, H, hd), inv_freq, scale)
    kk = _rope(mm(z, lw["wk"]).reshape(T, KV, hd), inv_freq, scale)
    v = mm(z, lw["wv"]).reshape(T, KV, hd)
    kk = jnp.repeat(kk, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    qr, kr, vr = rounded(q, quant), rounded(kk, quant), rounded(v, quant)
    nb = T // QUERY_BLOCK if T % QUERY_BLOCK == 0 else 1
    qb = qr.reshape(nb, T // nb, H, hd)
    keys = jnp.arange(T)[None, None, :]

    def block(args):
        i, qi = args
        rows = (i * (T // nb) + jnp.arange(T // nb))[None, :, None]
        s = jnp.einsum("shd,thd->hst", qi, kr, precision=HIGHEST) \
            / math.sqrt(hd)
        s = jnp.where((keys <= rows) & (keys > rows - window), s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hst,thd->shd", rounded(p, quant), vr,
                          precision=HIGHEST)

    att = jax.lax.map(block, (jnp.arange(nb), qb)).reshape(T, H * hd)
    h = x + mm(att, lw["wo"])
    y, _ = _moe(_norm(h, lw["ln2"], eps), lw, k, quant)
    return h + y


@functools.partial(jax.jit, static_argnames=("d", "quant"))
def _logits(x, w, d, quant):
    z = _norm(x, w["final_norm"], d[9])
    return jnp.dot(rounded(z, quant), rounded(w["head"], quant).T,
                   precision=HIGHEST)


def logits_at(cfg: Dict, weights: Dict[str, jax.Array], tokens: np.ndarray,
              rows: np.ndarray, quant: Optional[str] = None) -> jax.Array:
    """Logits (len(rows), vocab) of one sequence at positions ``rows``.

    The sequence is padded at its end to a whole number of query blocks;
    causal attention keeps every real position blind to the padding, and
    the padding's own routing touches no real row."""
    d = dims(cfg)
    windows, freqs, scales = layer_rope(cfg)
    T = len(tokens)
    Tp = -(-T // QUERY_BLOCK) * QUERY_BLOCK
    padded = np.zeros(Tp, np.int32)
    padded[:T] = tokens
    x = jnp.take(weights["embed"], jnp.asarray(padded), axis=0
                 ).astype(jnp.float32)
    for li in range(d[8]):
        x = _layer(x, weights, li, windows[li], freqs[li], scales[li], d,
                   quant)
    n = len(rows)
    picked = np.full(-(-n // 64) * 64, rows[-1], np.int64)
    picked[:n] = rows
    return _logits(x[jnp.asarray(picked)], weights, d, quant)[:n]


def _gaps(cfg, weights, prompt, served, quant):
    seq = np.concatenate([prompt, served]).astype(np.int32)
    rows = np.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
    ref = logits_at(cfg, weights, seq, rows)
    if quant:
        pick = jnp.argmax(logits_at(cfg, weights, seq, rows, quant), -1)
    else:
        pick = jnp.asarray(served)
    got = jnp.take_along_axis(ref, pick[:, None], 1)[:, 0]
    return np.asarray(jnp.max(ref, -1) - got)


def widest_gap(cfg: Dict, weights: Dict[str, jax.Array],
               pairs: Sequence[Tuple[np.ndarray, np.ndarray]],
               control: Optional[str] = None) -> float:
    """Widest gap, over (prompt, served) pairs, by which a served token's
    reference logit lies below the reference's best at its position;
    ``control`` reads, in place of the served tokens, those that the
    reference in that precision puts first."""
    worst = 0.0
    for prompt, served in pairs:
        worst = max(worst, float(np.max(
            _gaps(cfg, weights, prompt, served, control))))
    return worst
