"""Reference for a decoder-only dense transformer, and its weights.

The layer equations (pre-norm, as in Llama-style and StarCoder2 models)::

    h = x + Wo · attn(rope(Wq · n1(x)), rope(Wk · n1(x)), Wv · n1(x))
    y = h + mlp(n2(h))
    mlp(z) = Wd · (silu(Wg · z) * (Wu · z))        hidden_act "silu"
    mlp(z) = Wd · gelu_tanh(Wu · z)                hidden_act "gelu_pytorch_tanh"
    n(x)   = x / sqrt(mean(x^2) + eps) * w         RMS norm
    logits = n_f(x) · E^T                          E: the head (or the
                                                   embedding, when tied)

attention is causal over grouped key/value heads (query head h reads
key/value head h // (heads / kv_heads)), scaled by 1/sqrt(head_dim);
RoPE rotates the two halves of each head at position p by p * theta^(-2i/d).

It runs one sequence at a time and one layer at a time, queries in
blocks, so that it fits beside the served weights.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .precision import HIGHEST, key_for, rounded

QUERY_BLOCK = 512


def dims(cfg: Dict) -> Tuple:
    """The hashable sizes the jitted pieces are specialised on."""
    hd = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], hd, cfg["intermediate_size"],
            cfg["vocab_size"], cfg["num_hidden_layers"], cfg["hidden_act"],
            float(cfg["rms_norm_eps"]), float(cfg["rope_theta"]),
            bool(cfg["tie_word_embeddings"]))


# --------------------------------------------------------------------- #
# weights
# --------------------------------------------------------------------- #
def make_weights(cfg: Dict, seed: int) -> Dict[str, jax.Array]:
    """Random weights from ``seed``, bfloat16, on the device, in one
    jitted call.  Matrices are stored (in, out), stacked over layers."""
    return _make(dims(cfg), key_for(seed))


@functools.partial(jax.jit, static_argnums=0)
def _make(d, key):
    D, H, KV, hd, F, V, L, act, _, _, tied = d
    shapes = {"wq": (L, D, H * hd), "wk": (L, D, KV * hd),
              "wv": (L, D, KV * hd), "wo": (L, H * hd, D),
              "w_up": (L, D, F), "w_down": (L, F, D)}
    if act == "silu":
        shapes["w_gate"] = (L, D, F)
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes.items())):
        w = jax.random.normal(jax.random.fold_in(key, i), shape,
                              jnp.float32) / math.sqrt(shape[1])
        out[name] = w.astype(jnp.bfloat16)
    k = jax.random.fold_in(key, 100)
    out["embed"] = (0.02 * jax.random.normal(jax.random.fold_in(k, 0),
                                             (V, D))).astype(jnp.bfloat16)
    if not tied:
        out["head"] = (0.02 * jax.random.normal(
            jax.random.fold_in(k, 1), (V, D))).astype(jnp.bfloat16)
    # norm gains near one, as in a trained model, but not all one
    for j, (name, shape) in enumerate((("ln1", (L, D)), ("ln2", (L, D)),
                                       ("final_norm", (D,)))):
        g = 1.0 + 0.1 * jax.random.normal(jax.random.fold_in(k, 2 + j),
                                          shape)
        out[name] = g.astype(jnp.bfloat16)
    return out


# --------------------------------------------------------------------- #
# forward
# --------------------------------------------------------------------- #
def _norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _rope(x, theta):
    """x: (T, heads, hd) at positions 0..T-1."""
    T, _, hd = x.shape
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=("d", "quant"))
def _layer(x, w, li, d, quant):
    D, H, KV, hd, F, V, L, act, eps, theta, _ = d
    T = x.shape[0]

    def mm(a, b):
        return jnp.dot(rounded(a, quant), rounded(b, quant),
                       precision=HIGHEST)

    def lw(name):
        return w[name][li]

    z = _norm(x, lw("ln1"), eps)
    q = _rope(mm(z, lw("wq")).reshape(T, H, hd), theta)
    k = _rope(mm(z, lw("wk")).reshape(T, KV, hd), theta)
    v = mm(z, lw("wv")).reshape(T, KV, hd)
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    qr, kr, vr = rounded(q, quant), rounded(k, quant), rounded(v, quant)
    nb = T // QUERY_BLOCK if T % QUERY_BLOCK == 0 else 1
    qb = qr.reshape(nb, T // nb, H, hd)

    def block(args):
        i, qi = args
        rows = i * (T // nb) + jnp.arange(T // nb)
        s = jnp.einsum("shd,thd->hst", qi, kr, precision=HIGHEST) \
            / math.sqrt(hd)
        s = jnp.where(jnp.arange(T)[None, None, :] <= rows[None, :, None],
                      s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hst,thd->shd", rounded(p, quant), vr,
                          precision=HIGHEST)

    att = jax.lax.map(block, (jnp.arange(nb), qb)).reshape(T, H * hd)
    h = x + mm(att, lw("wo"))
    z = _norm(h, lw("ln2"), eps)
    if act == "silu":
        m = jax.nn.silu(mm(z, lw("w_gate"))) * mm(z, lw("w_up"))
    elif act == "gelu_pytorch_tanh":
        m = jax.nn.gelu(mm(z, lw("w_up")), approximate=True)
    else:
        raise ValueError(f"unknown hidden_act {act!r}")
    return h + mm(m, lw("w_down"))


@functools.partial(jax.jit, static_argnames=("d", "quant"))
def _logits(x, w, d, quant):
    eps, tied = d[8], d[10]
    head = w["embed"] if tied else w["head"]
    z = _norm(x, w["final_norm"], eps)
    return jnp.dot(rounded(z, quant), rounded(head, quant).T,
                   precision=HIGHEST)


def logits_at(cfg: Dict, weights: Dict[str, jax.Array], tokens: np.ndarray,
              rows: np.ndarray, quant: Optional[str] = None) -> jax.Array:
    """Logits (len(rows), vocab) of one sequence at positions ``rows``.

    The sequence is padded at its end to a whole number of query blocks;
    causal attention keeps every real position blind to the padding."""
    d = dims(cfg)
    T = len(tokens)
    Tp = -(-T // QUERY_BLOCK) * QUERY_BLOCK
    padded = np.zeros(Tp, np.int32)
    padded[:T] = tokens
    x = jnp.take(weights["embed"], jnp.asarray(padded), axis=0
                 ).astype(jnp.float32)
    for li in range(d[6]):
        x = _layer(x, weights, li, d, quant)
    # rows padded to a multiple of 64, so that few shapes compile
    n = len(rows)
    picked = np.full(-(-n // 64) * 64, rows[-1], np.int64)
    picked[:n] = rows
    return _logits(x[jnp.asarray(picked)], weights, d, quant)[:n]


def served_gaps(cfg: Dict, weights: Dict[str, jax.Array],
                prompt: np.ndarray, served: np.ndarray) -> np.ndarray:
    """For each served token: how far its reference logit lies below the
    reference's best at that position (0 where it is the argmax)."""
    seq = np.concatenate([prompt, served]).astype(np.int32)
    rows = np.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
    ref = logits_at(cfg, weights, seq, rows)
    got = jnp.take_along_axis(ref, jnp.asarray(served)[:, None], 1)[:, 0]
    return np.asarray(jnp.max(ref, -1) - got)


def control_gaps(cfg: Dict, weights: Dict[str, jax.Array],
                 prompt: np.ndarray, served: np.ndarray,
                 quant: str = "fp8") -> np.ndarray:
    """The control, at the same positions of the same tokens: the gap of
    the token that the ``quant`` reference puts first."""
    seq = np.concatenate([prompt, served]).astype(np.int32)
    rows = np.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
    ref = logits_at(cfg, weights, seq, rows)
    low = logits_at(cfg, weights, seq, rows, quant)
    pick = jnp.argmax(low, -1)
    got = jnp.take_along_axis(ref, pick[:, None], 1)[:, 0]
    return np.asarray(jnp.max(ref, -1) - got)


def widest_gap(cfg: Dict, weights: Dict[str, jax.Array],
               pairs: Sequence[Tuple[np.ndarray, np.ndarray]],
               control: Optional[str] = None) -> float:
    """Widest gap over (prompt, served) pairs; ``control`` reads the
    control in place of the served tokens."""
    worst = 0.0
    for prompt, served in pairs:
        g = control_gaps(cfg, weights, prompt, served, control) if control \
            else served_gaps(cfg, weights, prompt, served)
        worst = max(worst, float(np.max(g)))
    return worst
