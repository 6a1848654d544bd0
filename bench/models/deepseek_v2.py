"""DeepSeek-V2 (arXiv:2405.04434): multi-head latent attention (MLA) and
a fine-grained mixture of experts, routed softmax top-k plus shared
experts; the first ``first_k_dense_replace`` layers have a dense MLP.

The reference forward here is the published architecture in plain
float32, written from the model card's equations, as the configuration
file states it.  Departures from the Hugging Face modelling code, none of
which changes the function computed on these synthetic weights: rotary
dims pair split-half (the checkpoint pairs interleaved dims and permutes
them; a converter permutes the rope rows of ``wq``/``wkv_a``), and the
``n_shared_experts`` shared experts are one MLP of their summed width, as
the checkpoint stores them.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from bench.models import common as C



def dims(c):
    return dict(d=c["hidden_size"], nq=c["num_attention_heads"],
                dn=c["qk_nope_head_dim"], dr=c["qk_rope_head_dim"],
                dv=c["v_head_dim"], r=c["kv_lora_rank"],
                e=c["n_routed_experts"], k=c["num_experts_per_tok"],
                ffe=c["moe_intermediate_size"],
                ffs=c["moe_intermediate_size"] * c["n_shared_experts"],
                ff=c["intermediate_size"])


def program_config(c) -> dict:
    """Keyword arguments of the program's model configuration.  Routing
    is dropless: every expert's capacity is the whole batch, as the
    published model routes every token at inference."""
    if c.get("q_lora_rank"):
        raise ValueError("only q_lora_rank null (Lite) is supported")
    m = dims(c)
    return dict(name=c["name"], family="moe",
                n_layers=c["num_hidden_layers"], d_model=m["d"],
                n_heads=m["nq"], n_kv_heads=c["num_key_value_heads"],
                d_ff=m["ff"], vocab_size=c["vocab_size"],
                n_experts=m["e"], n_shared_experts=c["n_shared_experts"],
                top_k=m["k"], moe_d_ff=m["ffe"],
                first_dense_layers=c["first_k_dense_replace"],
                capacity_factor=m["e"] / m["k"],
                mla=True, kv_lora_rank=m["r"], q_lora_rank=0,
                qk_nope_head_dim=m["dn"], qk_rope_head_dim=m["dr"],
                v_head_dim=m["dv"], rope_theta=float(c["rope_theta"]),
                norm_eps=float(c["rms_norm_eps"]),
                tie_embeddings=bool(c["tie_word_embeddings"]))


def shapes(c):
    """(global weights, [(layer kind, layer weights)]) as name -> shape."""
    m = dims(c)
    d, nq = m["d"], m["nq"]
    glob = {"embed": (c["vocab_size"], d), "lm_head": (c["vocab_size"], d),
            "final_norm": (d,)}
    attn = {"attn_norm": (d,), "wq": (nq * (m["dn"] + m["dr"]), d),
            "wkv_a": (m["r"] + m["dr"], d), "kv_a_norm": (m["r"],),
            "wkv_b": (nq * (m["dn"] + m["dv"]), m["r"]),
            "wo": (d, nq * m["dv"]), "mlp_norm": (d,)}
    dense = dict(attn, w_gate=(m["ff"], d), w_up=(m["ff"], d),
                 w_down=(d, m["ff"]))
    moe = dict(attn, router=(m["e"], d),
               experts_w_gate=(m["e"], m["ffe"], d),
               experts_w_up=(m["e"], m["ffe"], d),
               experts_w_down=(m["e"], d, m["ffe"]),
               shared_w_gate=(m["ffs"], d), shared_w_up=(m["ffs"], d),
               shared_w_down=(d, m["ffs"]))
    nd = c["first_k_dense_replace"]
    return glob, ([("dense", dense)] * nd
                  + [("moe", moe)] * (c["num_hidden_layers"] - nd))


def quantized(name: str) -> bool:
    """Whether the served model stores this weight quantized (norms and
    the router stay in float)."""
    return not (name.endswith("norm") or name == "router")


def to_program(glob, layers):
    """The program's parameter tree, from per-layer named arrays."""
    attn_keys = ["wq", "wkv_a", "kv_a_norm", "wkv_b", "wo"]
    first = [{"attn_norm": lw["attn_norm"], "mlp_norm": lw["mlp_norm"],
              "attn": {k: lw[k] for k in attn_keys},
              "mlp": {k: lw[k] for k in ("w_gate", "w_up", "w_down")}}
             for kind, lw in layers if kind == "dense"]
    moe = [lw for kind, lw in layers if kind == "moe"]

    def stack(names, prefix=""):
        return {n: np.stack([lw[prefix + n] for lw in moe]) for n in names}
    ffn = ("w_gate", "w_up", "w_down")
    blocks = stack(["attn_norm", "mlp_norm"])
    blocks["attn"] = stack(attn_keys)
    blocks["moe"] = {"router": stack(["router"])["router"],
                     "experts": stack(ffn, "experts_"),
                     "shared": stack(ffn, "shared_")}
    out = {"embed": glob["embed"], "lm_head": glob["lm_head"],
           "final_norm": glob["final_norm"], "blocks": blocks}
    if first:
        out["first_blocks"] = first
    return out


def _mla(c, w, h, positions):
    m = dims(c)
    b, t, _ = h.shape
    nq, dn, dr, dv, r = m["nq"], m["dn"], m["dr"], m["dv"], m["r"]
    q = (h @ w["wq"].T).reshape(b, t, nq, dn + dr)
    q_nope, q_pe = q[..., :dn], q[..., dn:]
    kv_a = h @ w["wkv_a"].T
    ckv = C.rms_norm(kv_a[..., :r], w["kv_a_norm"], c["rms_norm_eps"])
    k_pe = kv_a[..., r:].reshape(b, t, 1, dr)
    kv = (ckv @ w["wkv_b"].T).reshape(b, t, nq, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    q_pe = C.rope(q_pe, positions, c["rope_theta"])
    k_pe = C.rope(k_pe, positions, c["rope_theta"])
    qf = jnp.concatenate([q_nope, q_pe], axis=-1)
    kf = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (b, t, nq, dr))],
                         axis=-1)
    o = C.causal_attention(qf, kf, v).reshape(b, t, nq * dv)
    return o @ w["wo"].T


def _moe(c, w, h):
    m = dims(c)
    b, t, d = h.shape
    x = h.reshape(b * t, d)
    probs = jax.nn.softmax(x @ w["router"].T, axis=-1)
    top, idx = jax.lax.top_k(probs, m["k"])
    if m["k"] > 1 and c["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    else:
        top = top * c["routed_scaling_factor"]
    gates = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], idx].set(top)       # (N, E)

    def one(acc, ew):
        wg, wu, wd, g = ew
        return acc + g[:, None] * C.swiglu(x, wg, wu, wd), None
    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (w["experts_w_gate"], w["experts_w_up"],
                         w["experts_w_down"], gates.T))
    y = y + C.swiglu(x, w["shared_w_gate"], w["shared_w_up"],
                     w["shared_w_down"])
    return y.reshape(b, t, d)


def layer(c, kind, w, x, positions):
    """One decoder layer in float32.  x: (B, T, d)."""
    eps = c["rms_norm_eps"]
    x = x + _mla(c, w, C.rms_norm(x, w["attn_norm"], eps), positions)
    h = C.rms_norm(x, w["mlp_norm"], eps)
    if kind == "dense":
        return x + C.swiglu(h, w["w_gate"], w["w_up"], w["w_down"])
    return x + _moe(c, w, h)


def head(c, glob, x):
    h = C.rms_norm(x, glob["final_norm"], c["rms_norm_eps"])
    return h @ glob["lm_head"].T


def embed(glob, tokens):
    return jnp.take(glob["embed"], tokens, axis=0)


def matmul_params(c) -> int:
    """Weights each token multiplies in the decoder layers: attention
    (MLA's up-projection counted once per token, as the absorbed decode
    also costs), the router, the ``num_experts_per_tok`` routed experts it
    is sent to and the shared experts; the dense layers' MLP."""
    m = dims(c)
    d = m["d"]
    attn = (d * m["nq"] * (m["dn"] + m["dr"]) + d * (m["r"] + m["dr"])
            + m["r"] * m["nq"] * (m["dn"] + m["dv"]) + m["nq"] * m["dv"] * d)
    dense = 3 * d * m["ff"]
    moe = d * m["e"] + 3 * d * m["ffe"] * m["k"] + 3 * d * m["ffs"]
    nd = c["first_k_dense_replace"]
    n = c["num_hidden_layers"]
    return n * attn + nd * dense + (n - nd) * moe


def attention(c):
    """(layers, heads, query-key width, value width) of the attention."""
    m = dims(c)
    return c["num_hidden_layers"], m["nq"], m["dn"] + m["dr"], m["dv"]


# MLA reads its up-projection whole each step and folds it into the query
# and the output (the absorbed decode): no activation is multiplied by the
# compressed ``wkv_b`` through the fused kernel.
MATERIALIZED = ("wkv_b",)

# Matrices that write to the residual stream.
RESIDUAL_OUT = ("wo", "w_down", "experts_w_down", "shared_w_down")
