"""Plain float32 building blocks shared by the reference models.

Nothing here imports the program under test.  Every matmul runs at
``highest`` precision (the caller sets ``jax.default_matmul_precision``),
so on a TPU a float32 product is not silently computed in bfloat16.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def rope(x, positions, theta):
    """Rotary embedding, split-half pairing: dims i and i + d/2 rotate
    together.  x: (B, T, H, d); positions: (T,)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None] * freqs       # (T, half)
    c, s = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def causal_attention(q, k, v):
    """q: (B, T, Hq, dq), k: (B, T, Hkv, dq), v: (B, T, Hkv, dv); query
    head h reads key/value head h // (Hq // Hkv).  Scale 1/sqrt(dq)."""
    b, t, hq, dq = q.shape
    rep = hq // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(dq)
    mask = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def swiglu(x, w_gate, w_up, w_down):
    """Weights are stored (out, in), as checkpoints store them."""
    g = x @ w_gate.T
    u = x @ w_up.T
    return (jax.nn.silu(g) * u) @ w_down.T


def fake_quant(w, bits: int):
    """Round a (…, out, in) float matrix to ``bits``-bit codes per output
    row, asymmetric over the row's [min, max] (zero included), and back:
    the weight a ``bits``-bit per-channel quantized model would serve."""
    levels = 2 ** bits - 1
    lo = jnp.minimum(jnp.min(w, axis=-1, keepdims=True), 0.0)
    hi = jnp.maximum(jnp.max(w, axis=-1, keepdims=True), 0.0)
    scale = jnp.maximum(hi - lo, 1e-12) / levels
    zero = jnp.round(-lo / scale)
    q = jnp.clip(jnp.round(w / scale) + zero, 0, levels)
    return (q - zero) * scale
