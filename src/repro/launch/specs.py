"""Dry-run spec planning — ShapeDtypeStruct stand-ins, zero allocation.

``param_specs(cfg, mode)`` builds the full-model parameter spec tree via
``jax.eval_shape`` over the real initializers (so dry-run shapes can never
drift from the real model), then rewrites policy-selected leaves into
QuantLinear/PackedLinear spec containers for the serve modes.

``input_specs(arch_id, shape_name)`` yields the four assigned input-shape
cells; serve shapes include the KV-cache spec trees.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import CompressionPolicy
from repro.core.compressed import (planned_packed_specs, planned_quant_specs,
                                   planned_tiled_specs, lut_spec)
from repro.models import lm as LM
from repro.models import encdec as ED
from repro.train.optimizer import AdamWConfig, QMoment


# The four assigned LM shapes: (name, seq_len, global_batch, kind)
SHAPES = {
    "train_4k":    dict(seq=4_096, batch=256, kind="train"),
    "prefill_32k": dict(seq=32_768, batch=32, kind="prefill"),
    "decode_32k":  dict(seq=32_768, batch=128, kind="decode"),
    "long_500k":   dict(seq=524_288, batch=1, kind="decode"),
}


def shape_applicable(cfg, shape_name: str) -> tuple[bool, str]:
    """Per DESIGN.md §Arch-applicability."""
    if shape_name == "long_500k" and not cfg.supports_long_context:
        return False, "long_500k skipped: full quadratic attention"
    return True, ""


def dense_param_specs(cfg, dtype=jnp.bfloat16) -> Any:
    if cfg.family == "encdec":
        fn = partial(ED.init_encdec, cfg=cfg, dtype=dtype)
    else:
        fn = partial(LM.init_lm, cfg=cfg, dtype=dtype)
    return jax.eval_shape(lambda: fn(jax.random.PRNGKey(0)))


def serve_param_specs(cfg, policy: CompressionPolicy,
                      dtype=jnp.bfloat16,
                      model_shards: int = 1) -> tuple[Any, Any]:
    """(param specs with containers, lut spec or None).

    ``model_shards``: intended weight-axis size (model×pod) of the serving
    mesh — planned planes then carry the fused tile-major layout whose
    tiles divide the per-shard out dim (``choose_fused_tiles(shards=…)``),
    exactly like ``engine.build_serve_params(model_shards=…)``, so the
    dry-run lowers the fused megakernel paths, not the two-step fallback.
    Stacked expert leaves keep stacked PackedLinear planes (never 2D-TP
    column tiles) so the grouped expert megakernel path stays reachable.
    """
    from repro.core.blocked_codec import choose_fused_tiles
    from repro.serve.engine import tile_shards

    dense = dense_param_specs(cfg, dtype)
    flat, treedef = jax.tree_util.tree_flatten_with_path(dense)
    out, any_compressed = [], False
    for path, leaf in flat:
        name = jax.tree_util.keystr(path)
        if leaf.ndim < 2:
            out.append(leaf)
            continue
        shape2 = tuple(leaf.shape[-2:])
        lead = tuple(leaf.shape[:-2])
        act = policy.action(name, shape2)
        if act == "quant":
            out.append(planned_quant_specs(shape2, stacked=lead))
        elif act == "compressed":
            any_compressed = True
            if (policy.tiles > 1 and shape2[1] % policy.tiles == 0
                    and "experts" not in name):
                in_t = shape2[1] // policy.tiles
                picked = choose_fused_tiles((shape2[0], in_t),
                                            policy.block_weights,
                                            shards=(model_shards, 1))
                tn, tk = picked[:2] if picked else (0, 0)
                out.append(planned_tiled_specs(
                    shape2, policy.tiles, stacked=lead,
                    block_weights=policy.block_weights,
                    tile_n=tn, tile_k=tk))
            else:
                from repro.sharding.partition import (clean_keystr,
                                                      is_row_parallel)
                picked = choose_fused_tiles(
                    shape2, policy.block_weights,
                    shards=tile_shards(name, model_shards))
                if picked is None:  # as build_serve_params: quant-only
                    out.append(planned_quant_specs(shape2, stacked=lead))
                    continue
                tn, tk = picked[:2]
                pl = planned_packed_specs(
                    shape2, stacked=lead,
                    block_weights=policy.block_weights,
                    tile_n=tn, tile_k=tk)
                pl.row_parallel = is_row_parallel(clean_keystr(name))
                out.append(pl)
        else:
            out.append(leaf)
    lut = lut_spec() if any_compressed else None
    return treedef.unflatten(out), lut


def train_state_specs(cfg, tcfg_optimizer: AdamWConfig,
                      param_dtype=jnp.bfloat16) -> Any:
    """{"params", "opt"} spec tree, honoring int8 optimizer state."""
    from repro.train.optimizer import moment_block, quantizable
    params = dense_param_specs(cfg, param_dtype)

    def mu(p):
        if quantizable(p, tcfg_optimizer):
            *lead, last = p.shape
            b = moment_block(last, tcfg_optimizer.qblock)
            q = jax.ShapeDtypeStruct((*lead, last // b, b), jnp.uint8)
            s = jax.ShapeDtypeStruct((*lead, last // b, 1), jnp.float32)
            return {"m": QMoment(q, s, s), "v": QMoment(q, s, s)}
        z = jax.ShapeDtypeStruct(p.shape, jnp.float32)
        return {"m": z, "v": z}

    opt = {"mu": jax.tree_util.tree_map(mu, params),
           "step": jax.ShapeDtypeStruct((), jnp.int32)}
    return {"params": params, "opt": opt}


def cache_specs_for(cfg, batch: int, max_len: int,
                    dtype=jnp.bfloat16) -> Any:
    if cfg.family == "encdec":
        hd = cfg.resolved_head_dim
        enc_len = _enc_len(cfg, max_len)
        sds = jax.ShapeDtypeStruct
        self_c = jax.eval_shape(
            lambda: ED.init_dec_caches(cfg, batch, max_len, dtype))
        ekv = sds((cfg.decoder_layers, batch, enc_len, cfg.n_kv_heads, hd),
                  dtype)
        return {"self": self_c, "enc_k": ekv, "enc_v": ekv}
    return jax.eval_shape(lambda: LM.init_caches(cfg, batch, max_len, dtype))


def _enc_len(cfg, seq: int) -> int:
    return seq  # audio frames length == assigned seq_len


def input_specs(arch_id: str, shape_name: str,
                dtype=jnp.bfloat16) -> dict:
    """Batch (and cache) ShapeDtypeStructs for one (arch × shape) cell.

    Returns {"kind", "batch": {...}, "caches": ... , "pos": ...} matching
    the step function the dry-run lowers.
    """
    entry = get_config(arch_id)
    cfg = entry.full
    sh = SHAPES[shape_name]
    seq, batch, kind = sh["seq"], sh["batch"], sh["kind"]
    sds = jax.ShapeDtypeStruct
    i32 = jnp.int32

    if kind == "train":
        if cfg.family == "encdec":
            b = {"enc_embeds": sds((batch, seq, cfg.d_model), dtype),
                 "tokens": sds((batch, seq), i32),
                 "labels": sds((batch, seq), i32)}
        elif cfg.family == "vlm":
            text = seq - cfg.n_patches
            b = {"tokens": sds((batch, text), i32),
                 "embeds": sds((batch, cfg.n_patches, cfg.d_model), dtype),
                 "labels": sds((batch, text), i32)}
        else:
            b = {"tokens": sds((batch, seq), i32),
                 "labels": sds((batch, seq), i32)}
        return {"kind": "train", "batch": b}

    if kind == "prefill":
        caches = cache_specs_for(cfg, batch, seq, dtype)
        out_caches = caches
        if cfg.family == "encdec":
            b = {"enc_embeds": sds((batch, seq, cfg.d_model), dtype),
                 "tokens": sds((batch, 1), i32)}
            caches = {"self": caches["self"]}  # enc_kv produced by prefill
        elif cfg.family == "vlm":
            b = {"tokens": sds((batch, seq - cfg.n_patches), i32),
                 "embeds": sds((batch, cfg.n_patches, cfg.d_model), dtype)}
        else:
            b = {"tokens": sds((batch, seq), i32)}
        return {"kind": "prefill", "batch": b, "caches": caches,
                "out_caches": out_caches}

    # decode: one new token against a seq-length cache
    caches = cache_specs_for(cfg, batch, seq, dtype)
    b = {"tokens": sds((batch, 1), i32)}
    return {"kind": "decode", "batch": b, "caches": caches,
            "pos": sds((), i32)}
