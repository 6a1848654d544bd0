"""InternLM2 (arXiv:2403.17297): dense decoder, grouped-query attention,
SwiGLU MLP, RMSNorm, rotary embeddings.

The reference forward here follows the published architecture in plain
float32.  Departures from the Hugging Face checkpoint layout, none of
which changes the function computed: the fused ``wqkv`` projection is kept
as separate ``wq``/``wk``/``wv`` rows (query head h reads key/value head
h // (n_heads / n_kv_heads), as the checkpoint's grouping does), and
weights are drawn synthetically in this layout.  Dynamic rope scaling, if
a checkpoint sets it, acts only beyond ``max_position_embeddings`` and is
not modelled.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from bench.models import common as C



def dims(c):
    d = c["hidden_size"]
    nq, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    return d, nq, nkv, d // nq


def program_config(c) -> dict:
    """Keyword arguments of the program's model configuration."""
    d, nq, nkv, hd = dims(c)
    return dict(name=c["name"], family="dense",
                n_layers=c["num_hidden_layers"], d_model=d, n_heads=nq,
                n_kv_heads=nkv, d_ff=c["intermediate_size"],
                vocab_size=c["vocab_size"], head_dim=hd,
                rope_theta=float(c["rope_theta"]),
                norm_eps=float(c["rms_norm_eps"]),
                tie_embeddings=bool(c["tie_word_embeddings"]))


def shapes(c):
    """(global weights, [(layer kind, layer weights)]) as name -> shape."""
    d, nq, nkv, hd = dims(c)
    ff, v = c["intermediate_size"], c["vocab_size"]
    glob = {"embed": (v, d), "lm_head": (v, d), "final_norm": (d,)}
    layer = {"attn_norm": (d,), "wq": (nq * hd, d), "wk": (nkv * hd, d),
             "wv": (nkv * hd, d), "wo": (d, nq * hd), "mlp_norm": (d,),
             "w_gate": (ff, d), "w_up": (ff, d), "w_down": (d, ff)}
    return glob, [("dense", layer)] * c["num_hidden_layers"]


def quantized(name: str) -> bool:
    """Whether the served model stores this weight quantized."""
    return not name.endswith("norm")


def to_program(glob, layers):
    """The program's parameter tree, from per-layer named arrays."""
    def stack(names, keys=None):
        return {k: np.stack([lw[n] for _, lw in layers])
                for k, n in zip(keys or names, names)}
    blocks = stack(["attn_norm", "mlp_norm"])
    blocks["attn"] = stack(["wq", "wk", "wv", "wo"])
    blocks["mlp"] = stack(["w_gate", "w_up", "w_down"])
    return {"embed": glob["embed"], "lm_head": glob["lm_head"],
            "final_norm": glob["final_norm"], "blocks": blocks}


def layer(c, kind, w, x, positions):
    """One decoder layer in float32.  x: (B, T, d)."""
    d, nq, nkv, hd = dims(c)
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    b, t, _ = x.shape
    h = C.rms_norm(x, w["attn_norm"], eps)
    q = (h @ w["wq"].T).reshape(b, t, nq, hd)
    k = (h @ w["wk"].T).reshape(b, t, nkv, hd)
    v = (h @ w["wv"].T).reshape(b, t, nkv, hd)
    q, k = C.rope(q, positions, theta), C.rope(k, positions, theta)
    o = C.causal_attention(q, k, v).reshape(b, t, nq * hd)
    x = x + o @ w["wo"].T
    h = C.rms_norm(x, w["mlp_norm"], eps)
    return x + C.swiglu(h, w["w_gate"], w["w_up"], w["w_down"])


def head(c, glob, x):
    """Final norm and LM head over selected hidden rows (N, d)."""
    h = C.rms_norm(x, glob["final_norm"], c["rms_norm_eps"])
    return h @ glob["lm_head"].T


def embed(glob, tokens):
    return jnp.take(glob["embed"], tokens, axis=0)


def matmul_params(c) -> int:
    """Weights each token multiplies in the decoder layers."""
    d, nq, nkv, hd = dims(c)
    per = d * nq * hd * 2 + d * nkv * hd * 2 + 3 * d * c["intermediate_size"]
    return per * c["num_hidden_layers"]


def attention(c):
    """(layers, heads, query-key width, value width) of the attention."""
    d, nq, nkv, hd = dims(c)
    return c["num_hidden_layers"], nq, hd, hd


MATERIALIZED = ()

# Matrices that write to the residual stream.
RESIDUAL_OUT = ("wo", "w_down")
