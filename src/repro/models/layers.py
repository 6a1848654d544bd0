"""Model primitives — functional layers over plain param pytrees.

Every linear weight is stored ``(out, in)`` and may be a dense array, a
``QuantLinear`` (int8) or a ``PackedLinear`` (Tiny-QMoE compressed); the
``linear`` dispatcher below routes to the fused kernels, which is how the
paper's technique becomes a first-class property of *every* architecture in
the zoo rather than a bolt-on.  Tile-laid ``PackedLinear`` /
``TiledPackedLinear`` weights (``tile_n > 0``) hit the
decode→dequant→matmul megakernel through ``ops.decode_dequant_matmul`` /
``ops.tiled_decode_dequant_matmul`` on single devices AND under sharded
meshes (a shard_map wrapper splits the fused grid per device; see the
mesh-dispatch rules on those ops) — the dense weight never materializes;
pass ``impl='unfused'`` to force the legacy two-step path.  Stacked MoE
expert weights — where ~all of a QMoE-class model's bytes live — go
through the grouped expert megakernel (``_expert_ffn`` →
``ops.grouped_decode_dequant_matmul``), so the compressed-resident
invariant holds for expert stacks too: peak HBM = compressed experts +
gathered activations + one VMEM tile.

Param trees are plain nested dicts so that (a) ``lax.scan`` over stacked
layers works out of the box, (b) sharding rules match on path names, and
(c) checkpointing is pure numpy.
"""
from __future__ import annotations

import collections
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core.compressed import PackedLinear, QuantLinear, TiledPackedLinear
from repro.kernels import ops
from repro.sharding.partition import constrain

Params = Any  # nested dict pytree


# ---------------------------------------------------------------------------
# Linear dispatch — dense | int8 | compressed.
# ---------------------------------------------------------------------------

def linear(x: jax.Array, w, lut=None, bias=None, impl: str = "auto"):
    """y = x @ W.T (+ bias) for any weight container."""
    if isinstance(w, TiledPackedLinear):
        y = ops.tiled_decode_dequant_matmul(x, w, lut, out_dtype=x.dtype,
                                            impl=impl)
    elif isinstance(w, PackedLinear):
        y = ops.decode_dequant_matmul(x, w, lut, out_dtype=x.dtype, impl=impl)
    elif isinstance(w, QuantLinear):
        y = ops.dequant_matmul(x, w.values, w.scale, w.zero,
                               out_dtype=x.dtype, impl=impl)
    else:
        y = jnp.einsum("...k,nk->...n", x, w.astype(x.dtype))
    if bias is not None:
        y = y + bias.astype(y.dtype)
    return y


# Trace-time materialization probe: which container classes decoded to a
# dense HBM tensor, keyed by kind ('packed', 'packed_stacked', 'tiled',
# 'quant').  'packed_stacked' is the expert-plane key — the grouped fused
# MoE path must keep it at zero (the acceptance invariant "zero
# materialize_weight calls on expert planes"); tests assert on it.
MATERIALIZE_COUNTS = collections.Counter()


def materialize_weight(w, lut=None, dtype=None):
    """Dense view of any weight container (unfused fallbacks, MLA absorb).

    ``dtype`` is honored identically on every container branch —
    ``None`` decodes PackedLinear/TiledPackedLinear *and* QuantLinear to
    bf16 (and leaves dense weights untouched); an explicit dtype is passed
    through unchanged everywhere.
    """
    if isinstance(w, (PackedLinear, TiledPackedLinear)):
        kind = "tiled" if isinstance(w, TiledPackedLinear) else "packed"
        if w.codes.ndim > (3 if kind == "tiled" else 2):
            kind += "_stacked"
        MATERIALIZE_COUNTS[kind] += 1
        return w.materialize(lut, jnp.bfloat16 if dtype is None else dtype)
    if isinstance(w, QuantLinear):
        MATERIALIZE_COUNTS["quant"] += 1
        return w.materialize(jnp.bfloat16 if dtype is None else dtype)
    return w if dtype is None else w.astype(dtype)


def embed(w, ids: jax.Array, lut=None) -> jax.Array:
    """Embedding lookup from dense or int8 tables (rows = vocab)."""
    if isinstance(w, QuantLinear):
        rows = w.values[ids].astype(jnp.float32)
        return ((rows - w.zero[ids, 0][..., None]) *
                w.scale[ids, 0][..., None]).astype(jnp.bfloat16)
    if isinstance(w, PackedLinear):  # decode then gather (rare path)
        dense = w.materialize(lut, jnp.bfloat16)
        return dense[ids]
    return w[ids]


# ---------------------------------------------------------------------------
# Norms + RoPE.
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)).astype(dt)


def rope_tables(positions: jax.Array, head_dim: int, theta: float):
    """cos/sin tables for given (possibly traced) positions.

    ``positions``: (T,) — one position track shared by the whole batch —
    or (B, T) per-row tracks (the continuous-batching decode step, where
    every slot sits at its own offset).  Returns (..., hd/2) matching."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (B, T, H, hd) — rotate pairs (split-half convention).  cos/sin
    are (T, hd/2) shared across the batch or (B, T, hd/2) per-row."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if cos.ndim == 3:
        c = cos[:, :, None, :]
        s = sin[:, :, None, :]
    else:
        c = cos[None, :, None, :]
        s = sin[None, :, None, :]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate([xf1 * c - xf2 * s, xf2 * c + xf1 * s],
                           axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# GQA attention (qwen/llama/internlm family).
# ---------------------------------------------------------------------------

def init_attention(key, cfg, dtype=jnp.float32) -> Params:
    d = cfg.d_model
    hd = cfg.resolved_head_dim
    nq, nkv = cfg.n_heads, cfg.n_kv_heads
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(d)
    p = {
        "wq": jax.random.normal(k1, (nq * hd, d), dtype) * s,
        "wk": jax.random.normal(k2, (nkv * hd, d), dtype) * s,
        "wv": jax.random.normal(k3, (nkv * hd, d), dtype) * s,
        "wo": jax.random.normal(k4, (d, nq * hd), dtype) * (1.0 / math.sqrt(nq * hd)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((nq * hd,), dtype)
        p["bk"] = jnp.zeros((nkv * hd,), dtype)
        p["bv"] = jnp.zeros((nkv * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def init_kv_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16) -> Params:
    hd = cfg.resolved_head_dim
    if getattr(cfg, "kv_cache_bits", 16) == 8:
        # int8 cache + per-(token, head) absmax scales (paper's quantizer
        # pointed at the KV cache — beyond-paper; halves decode bandwidth)
        return {
            "k": jnp.zeros((batch, max_len, cfg.n_kv_heads, hd), jnp.int8),
            "v": jnp.zeros((batch, max_len, cfg.n_kv_heads, hd), jnp.int8),
            "k_scale": jnp.zeros((batch, max_len, cfg.n_kv_heads, 1),
                                 jnp.float32),
            "v_scale": jnp.zeros((batch, max_len, cfg.n_kv_heads, 1),
                                 jnp.float32),
        }
    return {
        "k": jnp.zeros((batch, max_len, cfg.n_kv_heads, hd), dtype),
        "v": jnp.zeros((batch, max_len, cfg.n_kv_heads, hd), dtype),
    }


def _quant_kv(x: jax.Array):
    """(B, T, H, hd) float → (int8 codes, f32 scales) per (token, head)."""
    m = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(m / 127.0, 1e-12)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale


def _dequant_kv(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    return (q.astype(jnp.float32) * scale).astype(dtype)


def _kv_write(dst: jax.Array, src: jax.Array, pos) -> jax.Array:
    """Write ``src`` (B, T, ...) into the cache ``dst`` (B, L, ...) at
    ``pos``.

    Scalar ``pos`` (train / one-shot serving): a dynamic-slice update at
    one shared offset.  Vector ``pos`` (B,) (the continuous-batching
    decode step — every slot at its own offset): a per-row scatter, which
    requires T == 1.
    """
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        return jax.lax.dynamic_update_slice_in_dim(dst, src, pos, axis=1)
    if src.shape[1] != 1:
        raise ValueError("per-slot (vector pos) cache writes decode one "
                         f"token at a time; got T={src.shape[1]}")
    return dst.at[jnp.arange(dst.shape[0]), pos].set(src[:, 0])


_BATCH = ("pod", "data")


def _model_axis_size() -> int:
    from repro.sharding.partition import current_mesh
    axis_sizes, _ = current_mesh()
    return axis_sizes.get("model", 1)


def _attend_full(q, k, v, causal: bool, impl: str, kv_chunk=None,
                 serving: bool = False):
    """Prefill/train attention: (B, T, H, hd) layout in, flash kernel inside.

    Model-axis placement must be CONSISTENT between q and k/v or SPMD
    reconciles the flash einsum with full-cache gathers (52 GiB at the 32k
    prefill; §Perf iteration 6):
      * kv heads divide TP   → all of q/k/v shard heads (classic TP).
      * GQA-narrow at SERVE (no backward) and q heads divide → q keeps its
        natural head TP, k/v replicate in bf16 (transient).  Avoids the
        cross-dim q reshard XLA lowers as a 4 GiB/layer f32 gather (§Perf
        P3: llama prefill −3 TiB).
      * GQA-narrow at TRAIN → q shards its TIME dim (context parallelism);
        replicated k/v would live through the backward (HBM 4.1→18.7
        GiB/dev, refuted §Perf 6b).

    ``kv_chunk``: override the jnp-flash chunk (probe compiles pass the full
    length so attention FLOPs are loop-free and visible to cost_analysis).
    """
    msize = _model_axis_size()
    hkv = k.shape[2]
    hq = q.shape[2]
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    if msize > 1 and hkv % msize == 0:
        qt = constrain(qt, _BATCH, "model", None, None)
        kt = constrain(kt, _BATCH, "model", None, None)
        vt = constrain(vt, _BATCH, "model", None, None)
    elif msize > 1 and serving and hq % msize == 0:
        qt = constrain(qt, _BATCH, "model", None, None)
        kt = constrain(kt, _BATCH, None, None, None)
        vt = constrain(vt, _BATCH, None, None, None)
    elif msize > 1:
        qt = constrain(qt, _BATCH, None, "model", None)
        kt = constrain(kt, _BATCH, None, None, None)
        vt = constrain(vt, _BATCH, None, None, None)
        # barrier: otherwise XLA hoists the flash body's f32 casts above
        # the reshard and gathers rope internals in f32 (2× the bytes)
        qt, kt, vt = jax.lax.optimization_barrier((qt, kt, vt))
    ot = ops.flash_attention(qt, kt, vt, causal=causal, impl=impl,
                             kv_chunk=kv_chunk)
    return ot.transpose(0, 2, 1, 3)


def _attend_cache_flash(q, cache_k, cache_v, pos, impl: str):
    """Prefill attention over an (updated) cache, flash semantics.

    The naive cached path materializes (T, L) logits — 128 GiB/dev at the
    32k prefill shape (§Perf iteration 2).  Flash with ``q_offset=pos``
    keeps the online-softmax running state only.
    """
    msize = _model_axis_size()
    def _c(x):
        if msize > 1 and x.shape[1] % msize == 0:
            return constrain(x, _BATCH, "model", None, None)
        return constrain(x, _BATCH, None, None, None)
    qt = _c(q.transpose(0, 2, 1, 3))
    kt = _c(cache_k.transpose(0, 2, 1, 3))
    vt = _c(cache_v.transpose(0, 2, 1, 3))
    ot = ops.flash_attention(qt, kt, vt, causal=True, q_offset=pos,
                             impl=impl)
    return ot.transpose(0, 2, 1, 3)


def _attend_cached(q, cache_k, cache_v, pos, t_new: int):
    """Decode attention over a cache: mask positions > pos+t_new-1.

    q: (B, T, Hq, hd); cache: (B, L, Hkv, hd); pos: scalar (traced ok) or
    per-row (B,) offsets (continuous batching).  Entries past a row's own
    position get -1e30 → exp underflows to exactly 0.0, so padded / stale
    cache regions contribute nothing — bitwise — to the softmax sums.
    """
    b, t, hq, hd = q.shape
    hkv = cache_k.shape[2]
    rep = hq // hkv
    lmax = cache_k.shape[1]
    qf = q.astype(jnp.float32).reshape(b, t, hkv, rep, hd)
    kf = cache_k.astype(jnp.float32)
    vf = cache_v.astype(jnp.float32)
    logits = jnp.einsum("btgrd,blgd->btgrl", qf, kf) / math.sqrt(hd)
    kpos = jnp.arange(lmax)
    qpos = jnp.asarray(pos)[..., None] + jnp.arange(t)   # (t,) or (B, t)
    mask = kpos <= qpos[..., None]                       # (t, L) or (B, t, L)
    mask = (mask[None, :, None, None, :] if mask.ndim == 2
            else mask[:, :, None, None, :])
    logits = jnp.where(mask, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("btgrl,blgd->btgrd", p, vf)
    return out.reshape(b, t, hq, hd).astype(q.dtype)


def apply_attention(p: Params, x: jax.Array, cfg, *, lut=None,
                    cache: Optional[Params] = None, pos=None,
                    causal: bool = True, impl: str = "auto"):
    """Returns (y, new_cache). ``cache=None`` → full (train/prefill no-cache)
    attention; with cache: writes k/v at ``pos`` then attends ≤ pos."""
    b, t, d = x.shape
    hd = cfg.resolved_head_dim
    nq, nkv = cfg.n_heads, cfg.n_kv_heads

    q = linear(x, p["wq"], lut, p.get("bq"), impl).reshape(b, t, nq, hd)
    k = linear(x, p["wk"], lut, p.get("bk"), impl).reshape(b, t, nkv, hd)
    v = linear(x, p["wv"], lut, p.get("bv"), impl).reshape(b, t, nkv, hd)

    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)

    pos0 = 0 if pos is None else pos
    if jnp.ndim(pos0) == 1 and t != 1:
        raise ValueError("vector (per-slot) pos supports single-token "
                         f"decode only; got T={t}")
    positions = jnp.asarray(pos0)[..., None] + jnp.arange(t)
    cos, sin = rope_tables(positions, hd, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)

    if cache is None:
        kvc = t if getattr(cfg, "unroll_stack", False) else None
        o = _attend_full(q, k, v, causal, impl, kv_chunk=kvc)
        new_cache = None
    else:
        if t == 1:
            # Decode: the fused shard-mapped projections emit y
            # column-sharded on model; reshaped to (B, 1, H, hd) that is an
            # inexpressible (heads × head_dim) fragment, and SPMD
            # reconciles it with the cache layout by fully rematerializing
            # the multi-GiB KV cache every step (dry-run decode
            # collectives 6 MiB → 1.3 TiB when unpinned).  Pin the tiny
            # fresh q/k/v to the cache's head placement — heads on model
            # when they divide, else replicated (constrain drops
            # non-dividing axes) — so the cache keeps its spec-time
            # sharding through the update.
            q = constrain(q, _BATCH, None, "model", None)
            k = constrain(k, _BATCH, None, "model", None)
            v = constrain(v, _BATCH, None, "model", None)
        int8_kv = cache["k"].dtype == jnp.int8
        if int8_kv:
            kq, ks = _quant_kv(k)
            vq, vs = _quant_kv(v)
            ck = _kv_write(cache["k"], kq, pos0)
            cv = _kv_write(cache["v"], vq, pos0)
            cks = _kv_write(cache["k_scale"], ks, pos0)
            cvs = _kv_write(cache["v_scale"], vs, pos0)
            new_cache = {"k": ck, "v": cv, "k_scale": cks, "v_scale": cvs}
            ck_f = _dequant_kv(ck, cks, q.dtype)
            cv_f = _dequant_kv(cv, cvs, q.dtype)
        else:
            ck = _kv_write(cache["k"], k.astype(cache["k"].dtype), pos0)
            cv = _kv_write(cache["v"], v.astype(cache["v"].dtype), pos0)
            new_cache = {"k": ck, "v": cv}
            ck_f, cv_f = ck, cv
        if t == 1:
            o = _attend_cached(q, ck_f, cv_f, pos0, t)
        elif t == cache["k"].shape[1]:
            # Full prefill: the fresh (batch/head-sharded) k, v ARE the
            # cache content — attending over them directly avoids chunk-
            # slicing the sequence-sharded cache (52 GiB of gathers at the
            # 32k prefill shape; §Perf iteration 6).
            o = _attend_full(q, k, v, causal, impl, serving=True)
        else:  # chunked prefill: flash over the cache, never (T, L) logits
            o = _attend_cache_flash(q, ck_f, cv_f, pos0, impl)

    # NOTE(§Perf P1, refuted): explicitly resharding o from context-parallel
    # (T) back to head sharding before wo made collectives WORSE (llama
    # prefill 4.85→5.88 TiB; XLA lowers the cross-dim reshard as an f32
    # gather, not an all-to-all).  Leave propagation alone here.
    y = linear(o.reshape(b, t, nq * hd), p["wo"], lut, impl=impl)
    return y, new_cache


def apply_cross_attention(p: Params, x: jax.Array, enc_k, enc_v, cfg, *,
                          lut=None, impl: str = "auto"):
    """Decoder cross-attention over precomputed encoder K/V (B, S, H, hd)."""
    b, t, d = x.shape
    hd = cfg.resolved_head_dim
    nq = cfg.n_heads
    q = linear(x, p["wq"], lut, p.get("bq"), impl).reshape(b, t, nq, hd)
    o = _attend_full(q, enc_k, enc_v, causal=False, impl=impl)
    return linear(o.reshape(b, t, nq * hd), p["wo"], lut, impl=impl)


def project_enc_kv(p: Params, enc_out: jax.Array, cfg, *, lut=None,
                   impl: str = "auto"):
    b, s, d = enc_out.shape
    hd = cfg.resolved_head_dim
    nkv = cfg.n_kv_heads
    k = linear(enc_out, p["wk"], lut, p.get("bk"), impl).reshape(b, s, nkv, hd)
    v = linear(enc_out, p["wv"], lut, p.get("bv"), impl).reshape(b, s, nkv, hd)
    return k, v


# ---------------------------------------------------------------------------
# MLA — DeepSeek latent attention (compressed KV cache).
# ---------------------------------------------------------------------------

def init_mla(key, cfg, dtype=jnp.float32) -> Params:
    d = cfg.d_model
    nq = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank
    ks = jax.random.split(key, 6)
    s = 1.0 / math.sqrt(d)
    p = {}
    if cfg.q_lora_rank:
        p["wq_a"] = jax.random.normal(ks[0], (cfg.q_lora_rank, d), dtype) * s
        p["q_a_norm"] = jnp.ones((cfg.q_lora_rank,), dtype)
        p["wq_b"] = jax.random.normal(ks[1], (nq * (dn + dr), cfg.q_lora_rank),
                                      dtype) / math.sqrt(cfg.q_lora_rank)
    else:
        p["wq"] = jax.random.normal(ks[0], (nq * (dn + dr), d), dtype) * s
    p["wkv_a"] = jax.random.normal(ks[2], (r + dr, d), dtype) * s
    p["kv_a_norm"] = jnp.ones((r,), dtype)
    p["wkv_b"] = jax.random.normal(ks[3], (nq * (dn + dv), r), dtype) / math.sqrt(r)
    p["wo"] = jax.random.normal(ks[4], (d, nq * dv), dtype) / math.sqrt(nq * dv)
    return p


def init_mla_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16) -> Params:
    return {
        "ckv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
        "krope": jnp.zeros((batch, max_len, cfg.qk_rope_head_dim), dtype),
    }


def _mla_q(p, x, cfg, lut, impl):
    b, t, _ = x.shape
    nq = cfg.n_heads
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    if cfg.q_lora_rank:
        qa = linear(x, p["wq_a"], lut, impl=impl)
        qa = rms_norm(qa, p["q_a_norm"], cfg.norm_eps)
        q = linear(qa, p["wq_b"], lut, impl=impl)
    else:
        q = linear(x, p["wq"], lut, impl=impl)
    q = q.reshape(b, t, nq, dn + dr)
    return q[..., :dn], q[..., dn:]


def apply_mla(p: Params, x: jax.Array, cfg, *, lut=None, cache=None,
              pos=None, impl: str = "auto"):
    """MLA attention; decode path uses the *absorbed* form so per-step cost
    scales with kv_lora_rank, matching the MLA memory/compute claim."""
    b, t, d = x.shape
    nq = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank
    pos0 = 0 if pos is None else pos
    if jnp.ndim(pos0) == 1 and t != 1:
        raise ValueError("vector (per-slot) pos supports single-token "
                         f"decode only; got T={t}")

    q_nope, q_rope = _mla_q(p, x, cfg, lut, impl)
    positions = jnp.asarray(pos0)[..., None] + jnp.arange(t)
    cos, sin = rope_tables(positions, dr, cfg.rope_theta)
    q_rope = apply_rope(q_rope, cos, sin)

    kv_a = linear(x, p["wkv_a"], lut, impl=impl)          # (b,t,r+dr)
    ckv = rms_norm(kv_a[..., :r], p["kv_a_norm"], cfg.norm_eps)
    k_rope = kv_a[..., r:].reshape(b, t, 1, dr)
    k_rope = apply_rope(k_rope, cos, sin).reshape(b, t, dr)

    wkv_b = materialize_weight(p["wkv_b"], lut, x.dtype)  # (nq*(dn+dv), r)
    wkv_b = wkv_b.reshape(nq, dn + dv, r)
    w_k = wkv_b[:, :dn]                                   # (nq, dn, r)
    w_v = wkv_b[:, dn:]                                   # (nq, dv, r)

    if cache is None:
        # Prefill/train: materialize per-head K/V (cheap at O(T) once).
        k_nope = jnp.einsum("btr,hdr->bthd", ckv.astype(x.dtype), w_k)
        v = jnp.einsum("btr,hdr->bthd", ckv.astype(x.dtype), w_v)
        k_full = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None], (b, t, nq, dr))],
            axis=-1)
        q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
        # _attend_full scales by 1/sqrt(dn+dr) == MLA's score scale already.
        kvc = t if getattr(cfg, "unroll_stack", False) else None
        o = _attend_full(q_full, k_full, v, causal=True, impl=impl,
                         kv_chunk=kvc)
        new_cache = None
        o = o.astype(x.dtype)
        y = linear(o.reshape(b, t, nq * dv), p["wo"], lut, impl=impl)
        return y, new_cache

    # Cache updates (prefill writes T latents at pos0, decode writes 1;
    # vector pos0 scatters per-slot rows — continuous batching).
    cckv = _kv_write(cache["ckv"], ckv.astype(cache["ckv"].dtype), pos0)
    ckrope = _kv_write(cache["krope"], k_rope.astype(cache["krope"].dtype),
                       pos0)

    if t > 1:
        # Prefill: materialize per-head K/V (O(L) once) and run flash — the
        # absorbed path below would build (T, L) score tensors (528 GiB/dev
        # at 32k; §Perf iteration 2).  Full prefill (t == cache len) reads
        # the fresh latents, not the sequence-sharded cache (§Perf iter 6).
        full = t == cckv.shape[1]
        src_kv = ckv if full else cckv
        src_rope = k_rope if full else ckrope
        lmax = src_kv.shape[1]
        k_nope = jnp.einsum("blr,hdr->blhd", src_kv.astype(x.dtype), w_k)
        v_full = jnp.einsum("blr,hdr->blhd", src_kv.astype(x.dtype), w_v)
        k_full = jnp.concatenate(
            [k_nope, jnp.broadcast_to(src_rope[:, :, None].astype(x.dtype),
                                      (b, lmax, nq, dr))], axis=-1)
        q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
        if full:
            o = _attend_full(q_full, k_full, v_full, causal=True, impl=impl)
        else:
            o = _attend_cache_flash(q_full, k_full, v_full, pos0, impl)
        o = o.astype(x.dtype)
        y = linear(o.reshape(b, t, nq * dv), p["wo"], lut, impl=impl)
        return y, {"ckv": cckv, "krope": ckrope}

    # Decode (absorbed): score = qc·ckv + qr·krope over cached latents.
    qc = jnp.einsum("bthd,hdr->bthr", q_nope.astype(jnp.float32),
                    w_k.astype(jnp.float32))               # (b,t,h,r)
    s_nope = jnp.einsum("bthr,blr->bthl", qc, cckv.astype(jnp.float32))
    s_rope = jnp.einsum("bthd,bld->bthl", q_rope.astype(jnp.float32),
                        ckrope.astype(jnp.float32))
    logits = (s_nope + s_rope) / math.sqrt(dn + dr)
    lmax = cckv.shape[1]
    kpos = jnp.arange(lmax)
    qpos = jnp.asarray(pos0)[..., None] + jnp.arange(t)  # (t,) or (B, t)
    mask = kpos <= qpos[..., None]                       # (t, L) or (B, t, L)
    mask = mask[None, :, None, :] if mask.ndim == 2 else mask[:, :, None, :]
    logits = jnp.where(mask, logits, -1e30)
    attn = jax.nn.softmax(logits, axis=-1)
    o_lat = jnp.einsum("bthl,blr->bthr", attn, cckv.astype(jnp.float32))
    o = jnp.einsum("bthr,hdr->bthd", o_lat, w_v.astype(jnp.float32))
    o = o.astype(x.dtype)
    y = linear(o.reshape(b, t, nq * dv), p["wo"], lut, impl=impl)
    return y, {"ckv": cckv, "krope": ckrope}


# ---------------------------------------------------------------------------
# SwiGLU MLP + MoE.
# ---------------------------------------------------------------------------

def init_mlp(key, d: int, ff: int, dtype=jnp.float32) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": jax.random.normal(k1, (ff, d), dtype) / math.sqrt(d),
        "w_up": jax.random.normal(k2, (ff, d), dtype) / math.sqrt(d),
        "w_down": jax.random.normal(k3, (d, ff), dtype) / math.sqrt(ff),
    }


def apply_mlp(p: Params, x: jax.Array, *, lut=None, impl: str = "auto"):
    g = linear(x, p["w_gate"], lut, impl=impl)
    u = linear(x, p["w_up"], lut, impl=impl)
    return linear(jax.nn.silu(g) * u, p["w_down"], lut, impl=impl)


def init_moe(key, cfg, dtype=jnp.float32) -> Params:
    d, e, ffe = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    p = {
        "router": jax.random.normal(k1, (e, d), dtype) / math.sqrt(d),
        "experts": {
            "w_gate": jax.random.normal(k2, (e, ffe, d), dtype) / math.sqrt(d),
            "w_up": jax.random.normal(k3, (e, ffe, d), dtype) / math.sqrt(d),
            "w_down": jax.random.normal(k4, (e, d, ffe), dtype) / math.sqrt(ffe),
        },
    }
    if cfg.n_shared_experts:
        p["shared"] = init_mlp(k5, d, cfg.moe_d_ff * cfg.n_shared_experts,
                               dtype)
    return p


def _capacity(n_tokens: int, top_k: int, n_experts: int, factor: float) -> int:
    c = int(math.ceil(n_tokens * top_k / n_experts * factor))
    return max(4, min(c, n_tokens))


def _grouped_fused_ok(w, lut) -> bool:
    """True when an expert stack can take the grouped fused megakernel:
    a stacked PackedLinear (leading expert axis) in tile-major layout with
    a decode LUT in hand."""
    return (isinstance(w, PackedLinear) and getattr(w, "tile_n", 0) > 0
            and w.codes.ndim == 3 and lut is not None)


def _expert_ffn(experts: Params, xe: jax.Array, lut=None,
                impl: str = "auto", *, local: bool = False) -> jax.Array:
    """SwiGLU over capacity-gathered per-expert token blocks (E, cap, d).

    The three expert matmuls route through the grouped fused
    decode→dequant→matmul megakernel whenever the stack is a compressed
    PackedLinear — dense expert weights never materialize in HBM
    (``ops.grouped_decode_dequant_matmul``, which also owns the mesh
    dispatch, the unfused fallback, and the 'grouped_*' probes).
    ``local=True`` marks a caller already inside a shard_map that owns
    only its expert shard (the local-routing MoE): the shard-local
    ``ops.grouped_fused_local`` runs directly, no nested mesh dispatch —
    the caller gates eligibility before choosing this path.  Dense and
    QuantLinear stacks fall back to materialize + einsum.
    """
    def mm(h, w):
        if isinstance(w, PackedLinear) and w.codes.ndim == 3 \
                and lut is not None:
            if local:
                if _grouped_fused_ok(w, lut):
                    return ops.grouped_fused_local(
                        h, w, lut, out_dtype=h.dtype, impl=impl)
                # linear-layout stack inside shard_map: materialize the
                # local shard below (no probe — ops owns probes)
            else:
                return ops.grouped_decode_dequant_matmul(
                    h, w, lut, out_dtype=h.dtype, impl=impl)
        return jnp.einsum("ecx,eyx->ecy", h,
                          materialize_weight(w, lut, h.dtype))

    g = mm(xe, experts["w_gate"])
    u = mm(xe, experts["w_up"])
    return mm(jax.nn.silu(g) * u, experts["w_down"])


def _moe_compute(xf, router_w, wg, wu, wd, cfg, n_experts: int,
                 expert_offset, *, lut=None, impl: str = "auto",
                 local: bool = False):
    """Core top-k dispatch + expert FFN over a token matrix (n_tok, d).

    ``n_experts``/``expert_offset``: the LOCAL expert range this caller
    owns (global dispatch: all of them, offset 0; shard_map local
    dispatch: E/model_size per device).  Router logits always span the
    FULL expert set so gates are identical across shards; slots routed
    outside [offset, offset+n_experts) are dropped locally (they are
    served by the owning shard).

    ``wg``/``wu``/``wd`` may be dense (local) arrays or stacked weight
    containers — the expert FFN goes through :func:`_expert_ffn`, so
    compressed stacks hit the grouped fused megakernel instead of
    materializing (``local`` marks shard_map callers).
    Returns (y (n_tok, d), aux_loss).
    """
    n_tok, d = xf.shape
    e_full = router_w.shape[0]
    k = cfg.top_k
    router_logits = jnp.einsum("td,ed->te", xf.astype(jnp.float32),
                               router_w.astype(jnp.float32))
    probs = jax.nn.softmax(router_logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)        # (n_tok, k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # Load-balance aux loss (Switch-style) over the FULL expert set.
    onehot = jax.nn.one_hot(expert_ids, e_full, dtype=jnp.float32)
    f = jnp.mean(jnp.sum(onehot, axis=1), axis=0)
    pmean = jnp.mean(probs, axis=0)
    aux = e_full * jnp.sum(f * pmean)

    cap = _capacity(n_tok, k, e_full, cfg.capacity_factor)

    local_ids = expert_ids - expert_offset
    owned = (local_ids >= 0) & (local_ids < n_experts)     # (n_tok, k)
    oh_local = jax.nn.one_hot(jnp.where(owned, local_ids, n_experts),
                              n_experts, dtype=jnp.float32)
    flat_e = jnp.where(owned, local_ids, n_experts).reshape(-1)
    onehot_flat = oh_local.reshape(n_tok * k, n_experts)
    pos_in_e = jnp.cumsum(onehot_flat, axis=0) - onehot_flat
    slot = jnp.sum(pos_in_e * onehot_flat, axis=-1).astype(jnp.int32)
    keep = (slot < cap) & owned.reshape(-1)
    slot_c = jnp.where(keep, slot, cap)
    flat_e_c = jnp.where(keep, flat_e, 0)

    tok_idx = jnp.repeat(jnp.arange(n_tok), k)
    table = jnp.full((n_experts, cap + 1), n_tok, jnp.int32)
    table = table.at[flat_e_c, slot_c].set(
        jnp.where(keep, tok_idx, n_tok), mode="drop")
    gtable = jnp.zeros((n_experts, cap + 1), jnp.float32)
    gtable = gtable.at[flat_e_c, slot_c].set(
        jnp.where(keep, gate_vals.reshape(-1), 0.0), mode="drop")
    table = table[:, :cap]
    gtable = gtable[:, :cap]

    xpad = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)], axis=0)
    xe = xpad[table]                                       # (e_loc, cap, d)
    ye = _expert_ffn({"w_gate": wg, "w_up": wu, "w_down": wd}, xe,
                     lut, impl, local=local)

    out = jnp.zeros((n_tok + 1, d), xf.dtype)
    out = out.at[table].add(ye.astype(xf.dtype) *
                            gtable[..., None].astype(xf.dtype))
    return out[:n_tok], aux


def apply_moe_local(p: Params, x: jax.Array, cfg, *, lut=None,
                    impl: str = "auto"):
    """shard_map local-routing MoE (§Perf DP3, beyond-paper).

    Tokens stay on their (pod, data) shard; experts live on their model
    shard; each device dispatches its local tokens to its local experts
    and the partial outputs psum over "model" in bf16 — replacing SPMD's
    dense global dispatch (full-token gathers + f32 (E,cap,d) combine
    all-reduces).  Capacity is per-(token-shard, expert): slightly
    different drop behaviour than the global path; equal when dropless.

    Compressed expert stacks (tile-major stacked PackedLinear) enter the
    shard_map as *planes* — expert axis on "model" — and each device runs
    the grouped fused decode→dequant→matmul megakernel over its resident
    E/model compressed slab (probe 'grouped_fused_shard_map'): dense
    expert weights never exist, on any device.  Other containers keep the
    legacy shape: materialize the dense stack outside, shard it on the
    expert dim.
    """
    from jax.sharding import PartitionSpec as P
    from repro.sharding.partition import current_mesh

    axis_sizes, mesh = current_mesh()
    msize = axis_sizes.get("model", 1)
    e_full = cfg.n_experts
    b, t, d = x.shape
    batch_axes = tuple(a for a in ("pod", "data") if a in axis_sizes)
    experts = p["experts"]
    # resolve the session-default 'unfused' lever here too: the grouped
    # gate below decides the path before any ops entry point would
    impl = ops._resolve_unfused(impl)
    grouped = (impl != "unfused" and e_full % msize == 0
               and all(_grouped_fused_ok(experts[k], lut)
                       for k in ("w_gate", "w_up", "w_down")))
    router_w = materialize_weight(p["router"], lut, jnp.float32)

    espec = P("model", None, None)
    xspec = P(batch_axes if batch_axes else None, None, None)

    def local_fn(x_loc, rw, lut_l, wg_l, wu_l, wd_l):
        bl, tl, _ = x_loc.shape
        xf = x_loc.reshape(bl * tl, d)
        midx = jax.lax.axis_index("model")
        y, aux = _moe_compute(xf, rw, wg_l, wu_l, wd_l, cfg,
                              e_full // msize, midx * (e_full // msize),
                              lut=lut_l, impl=impl, local=grouped)
        y = jax.lax.psum(y.astype(x_loc.dtype), "model")
        aux = jax.lax.pmean(aux, "model")
        if batch_axes:
            aux = jax.lax.pmean(aux, batch_axes)
        return y.reshape(bl, tl, d), aux

    if grouped:
        # Compressed planes cross into the shard_map expert-sharded: the
        # induced gather moves compressed bytes, never dense experts.
        ops.DISPATCH_COUNTS["grouped_fused_shard_map"] += 1
        wg_in, wu_in, wd_in = (experts[k]
                               for k in ("w_gate", "w_up", "w_down"))
        wspecs = tuple(
            jax.tree_util.tree_map(
                lambda a: P(*(("model",) + (None,) * (a.ndim - 1))), w)
            for w in (wg_in, wu_in, wd_in))
        lut_in, lspec = lut, P(None, None)
    else:
        wg_in, wu_in, wd_in = (
            jax.lax.with_sharding_constraint(
                materialize_weight(experts[k], lut, x.dtype),
                jax.NamedSharding(mesh, espec))
            for k in ("w_gate", "w_up", "w_down"))
        wspecs = (espec, espec, espec)
        # dense path never touches the LUT inside; a 1-byte dummy keeps the
        # shard_map signature uniform
        lut_in, lspec = jnp.zeros((1, 1), jnp.uint8), P(None, None)

    y, aux = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(xspec, P(None, None), lspec) + wspecs,
        out_specs=(xspec, P()),
        check_vma=False,
    )(x, router_w, lut_in, wg_in, wu_in, wd_in)

    if "shared" in p:
        y = y + apply_mlp(p["shared"], x.reshape(b * t, d), lut=lut,
                          impl=impl).reshape(b, t, d)
    return y, aux


def apply_moe(p: Params, x: jax.Array, cfg, *, lut=None, impl: str = "auto",
              with_routing: bool = False):
    """Capacity-based top-k MoE with sort-free scatter dispatch.

    Returns (y, aux_loss).  Dropless up to ``capacity_factor``; overflow
    tokens fall through to the shared experts / residual (standard
    capacity-drop semantics).

    ``with_routing=True`` additionally returns the raw top-k expert ids
    (n_tok, k) int32 — the tiered-residency manager (serve/residency.py)
    reads them host-side to decide which experts the next step needs.
    Routing forces the global dispatch path (the local shard_map path has
    no single routing tensor to return).

    When ``p["residency"]`` is present (a per-layer ``{"slot_of_expert",
    "expert_of_slot"}`` pair of int32 maps installed by the residency
    manager), the expert stacks in ``p["experts"]`` hold only the
    HBM-cached *slots*: routed activations are gathered into slot order,
    the grouped kernel runs over the C-slot stacks, and outputs scatter
    back to expert order.  Absent experts read out-of-bounds and fill
    with exact zeros — the manager guarantees every *routed* expert is
    resident before a step commits, so those zero rows only ever multiply
    zero gates and the combine stays bitwise-equal to the fully-resident
    path.
    """
    if getattr(cfg, "moe_local_dispatch", False) and not with_routing \
            and p.get("residency") is None:
        from repro.sharding.partition import current_mesh
        axis_sizes, mesh = current_mesh()
        msize = axis_sizes.get("model", 1)
        bsize = 1
        for a in ("pod", "data"):
            bsize *= axis_sizes.get(a, 1)
        if (mesh is not None and hasattr(mesh, "devices") and msize > 1
                and cfg.n_experts % msize == 0
                and x.shape[0] % bsize == 0):
            return apply_moe_local(p, x, cfg, lut=lut, impl=impl)
        # no concrete mesh / non-divisible batch: global dispatch below
    b, t, d = x.shape
    n_tok = b * t
    e, k = cfg.n_experts, cfg.top_k
    xf = x.reshape(n_tok, d)

    router_logits = linear(xf, p["router"], lut, impl=impl).astype(jnp.float32)
    probs = jax.nn.softmax(router_logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)        # (n_tok, k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # Load-balance aux loss (Switch-style): e * Σ_e f_e · P_e.
    onehot = jax.nn.one_hot(expert_ids, e, dtype=jnp.float32)  # (n,k,e)
    f = jnp.mean(jnp.sum(onehot, axis=1), axis=0)
    pmean = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(f * pmean)

    cap = _capacity(n_tok, k, e, cfg.capacity_factor)

    # Position of each (token, slot) within its expert queue.
    flat_e = expert_ids.reshape(-1)                        # (n·k,)
    onehot_flat = onehot.reshape(n_tok * k, e)
    pos_in_e = (jnp.cumsum(onehot_flat, axis=0) - onehot_flat)  # counts before
    slot = jnp.sum(pos_in_e * onehot_flat, axis=-1).astype(jnp.int32)  # (n·k,)
    keep = slot < cap
    slot_c = jnp.where(keep, slot, cap)                    # cap → dropped (OOB)

    # Scatter token indices into the (e, cap) dispatch table.
    tok_idx = jnp.repeat(jnp.arange(n_tok), k)
    table = jnp.full((e, cap), n_tok, jnp.int32)           # n_tok = zero row
    table = table.at[flat_e, slot_c].set(tok_idx, mode="drop")
    gtable = jnp.zeros((e, cap), jnp.float32)
    gtable = gtable.at[flat_e, slot_c].set(gate_vals.reshape(-1), mode="drop")

    xpad = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)], axis=0)
    xe = xpad[table]                                       # (e, cap, d)
    # EP: dispatch table and expert activations shard on the expert dim —
    # SPMD otherwise replicates the (e, cap, d) gather (60 GiB/dev at the
    # 32k prefill shape; §Perf iteration 3).  The induced collective is the
    # token all-to-all any EP implementation pays.
    xe = constrain(xe, "model", None, None)

    res = p.get("residency")
    if getattr(cfg, "moe_expert_scan", False) and res is None:
        # Paper's decompress-on-demand at *expert* granularity: scan over
        # experts, decode one expert's weights at a time — peak memory is
        # (all experts compressed) + (one expert dense), the MoE analogue
        # of the paper's layer-by-layer decompression.  Single-device edge
        # mode; under EP sharding prefer the vectorized path below (each
        # device decodes only its expert shard).
        def expert_body(_, inp):
            wg_e, wu_e, wd_e, x_e = inp
            wg_d = materialize_weight(wg_e, lut, x.dtype)
            wu_d = materialize_weight(wu_e, lut, x.dtype)
            wd_d = materialize_weight(wd_e, lut, x.dtype)
            g = x_e @ wg_d.T
            u = x_e @ wu_d.T
            return None, (jax.nn.silu(g) * u) @ wd_d.T

        _, ye = jax.lax.scan(
            expert_body, None,
            (p["experts"]["w_gate"], p["experts"]["w_up"],
             p["experts"]["w_down"], xe))
    elif res is not None:
        # Tiered residency: only the HBM-cached slots carry expert planes.
        # Gather routed activations into slot order (vacant slots — sentinel
        # index E, out of bounds — fill with zeros), run the grouped kernel
        # over the C-slot stacks, scatter back to expert order (absent
        # experts — sentinel index C — fill with zeros, multiplied below by
        # their all-zero gtable rows).  Per-expert kernel tiles see exactly
        # the bytes and activations the fully-resident stack would give
        # them, so resident rows are bitwise-identical.
        xe_c = jnp.take(xe, res["expert_of_slot"], axis=0,
                        mode="fill", fill_value=0)         # (C, cap, d)
        ye_c = _expert_ffn(p["experts"], xe_c, lut, impl)
        ye = jnp.take(ye_c, res["slot_of_expert"], axis=0,
                      mode="fill", fill_value=0)           # (e, cap, d)
    else:
        # Grouped fused expert FFN: compressed stacks stream through the
        # expert-grid megakernel (shard-mapped onto the model axis under a
        # concrete mesh) instead of materializing (E, ffe, d) dense — see
        # _expert_ffn / ops.grouped_decode_dequant_matmul.
        ye = _expert_ffn(p["experts"], xe, lut, impl)      # (e, cap, d)

    ye = constrain(ye, "model", None, None)
    # empty table entries point one past the last token: dropped
    y = jnp.zeros((n_tok, d), x.dtype).at[table].add(
        ye * gtable[..., None].astype(x.dtype), mode="drop")

    if "shared" in p:
        y = y + apply_mlp(p["shared"], xf, lut=lut, impl=impl)
    y = y.reshape(b, t, d)
    if with_routing:
        return y, aux, expert_ids
    return y, aux
