"""Whether the served tokens are right, judged by the plain reference.

After the window, a sample of the requests the window finished, drawn
from the seed with the longest always in it and the longest of those the
window admitted, is run through the float32
reference (``bench/models/<family>.py``) over each prompt with its served
tokens.  For each served token the gap is how far the reference's logit
of that token lies below the reference's best logit; the number compared
is the widest gap.  Greedy decoding puts the program's best logit first,
so a sound program reads a gap of rounding size, and one that computes
something else reads far more.

The reference takes nothing the program made: it draws the weights again
from the seed, one layer at a time, on the device, after the program's
state is freed, so that it fits.  Its control (``control_bits``) is the
same reference with every matrix the program quantizes rounded to a
coarser grid, read at the same positions: the gap of the token that the
control puts first.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from bench import weights as W
from bench.models import common


def sample(recs, seed: int, want_tokens: int, window_step: int) -> list:
    """Finished requests to compare: the longest (prompt + served), the
    longest of those admitted in the window (from ``window_step`` on),
    then others in the seed's order until ``want_tokens`` served tokens."""
    if not recs:
        return []
    recs = sorted(recs, key=lambda r: r.rid)

    def longest(rs):
        return max(rs, key=lambda r: (r.prompt_len + r.max_new, -r.rid))
    out = [longest(recs)]
    late = [r for r in recs if r.admit_step >= window_step]
    if late and longest(late) is not out[0]:
        out.append(longest(late))
    rest = [r for r in recs if all(r is not o for o in out)]
    order = np.random.default_rng(int(seed) ^ 0xC0FFEE).permutation(len(rest))
    n = sum(r.completion.n_generated for r in out)
    for i in order:
        if n >= want_tokens:
            break
        out.append(rest[i])
        n += rest[i].completion.n_generated
    return out


@functools.lru_cache(maxsize=None)
def _layer_fn(model, ckey, kind):
    c = dict(ckey)

    @jax.jit
    def f(w, x, positions):
        with jax.default_matmul_precision("highest"):
            return model.layer(c, kind, w, x, positions)
    return f


def _as_f32(tree, model, bits):
    out = {}
    for k, v in tree.items():
        v = v.astype(jnp.float32)
        if bits and v.ndim >= 2 and model.quantized(k):
            v = common.fake_quant(v, bits)
        out[k] = v
    return out


def reference_logits(model, c, seed: int, seqs, rows, *, bits=None,
                     device=None):
    """Logits (N, vocab) of the reference at the given (sequence, position)
    rows.  seqs: list of 1-D token arrays (prompt + served tokens);
    bits: None for the reference, else the control's weight bits."""
    device = device or jax.devices()[0]
    glob_shapes, layers = model.shapes(c)
    ckey = tuple(sorted((k, v) for k, v in c.items()
                        if isinstance(v, (int, float, str, bool))
                        or v is None))
    t = max(len(s) for s in seqs)
    toks = np.zeros((len(seqs), t), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    args = (len(layers), model.RESIDUAL_OUT)
    glob = _as_f32(W.draw(seed, 0, glob_shapes, *args, device=device),
                   model, bits)
    positions = jnp.arange(t)
    x = model.embed(glob, jax.device_put(toks, device))
    for i, (kind, shp) in enumerate(layers):
        w = _as_f32(W.draw(seed, i + 1, shp, *args, device=device), model,
                    bits)
        x = _layer_fn(model, ckey, kind)(w, x, positions)
        del w
    bi = jnp.asarray([b for b, _ in rows])
    pi = jnp.asarray([p for _, p in rows])
    with jax.default_matmul_precision("highest"):
        return model.head(c, glob, x[bi, pi])


def served_rows(picked):
    """(sequences, rows, served tokens): each served token i of a request
    is predicted at position prompt_len - 1 + i of prompt + served."""
    seqs, rows, served = [], [], []
    for b, r in enumerate(picked):
        toks = np.asarray(r.completion.tokens, np.int32)
        seqs.append(toks[:-1])
        out = toks[r.prompt_len:]
        for i, tok in enumerate(out):
            rows.append((b, r.prompt_len - 1 + i))
            served.append(int(tok))
    return seqs, rows, np.asarray(served)


def gaps(ref_logits, tokens) -> np.ndarray:
    """How far each token's reference logit lies below the best."""
    ref = np.asarray(ref_logits, np.float64)
    return ref.max(axis=1) - ref[np.arange(len(tokens)), tokens]
