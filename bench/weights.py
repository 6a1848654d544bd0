"""Synthetic weights drawn from a seed, shared by the served model and the
reference.

Every matrix is drawn from a Laplace law rounded to bfloat16, the type
checkpoints are published in (handed over as the float32 of those
bfloat16 values): heavy-tailed rows like a trained model's, so
per-channel int8 codes crowd near the zero point and the dictionary
compresses them as it would a real checkpoint (normal draws escape nearly
every gram).  The standard deviation is 1/sqrt(fan-in), and
1/sqrt(2 * layers) more on matrices that write to the residual stream
(``std``); the embedding's is 1.  Norm scales are ones.  One layer is
drawn per jitted call, on the device, so the dense weights never sit on
the chip all at once and the peak-memory reading of a run sees the
compressed model only.
"""
from __future__ import annotations

import functools
import math
import zlib

import jax
import jax.numpy as jnp



def seed_key(seed: int):
    """A PRNG key from any non-negative integer: the low and the high 32
    bits both count (``PRNGKey`` alone keeps only the low ones)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def std(name: str, shape, n_layers: int, residual_out=()) -> float:
    """Standard deviation of a weight (0 for a norm scale, drawn as ones)."""
    if len(shape) < 2:
        return 0.0
    if name == "embed":
        return 1.0
    s = 1.0 / math.sqrt(shape[-1])
    if name in residual_out:
        s /= math.sqrt(2 * n_layers)
    return s


@functools.partial(jax.jit, static_argnames=("spec",))
def _draw(key, *, spec):
    out = {}
    for name, shape, sd in spec:
        if sd:
            k = jax.random.fold_in(key, zlib.crc32(name.encode()))
            b = sd / math.sqrt(2.0)              # Laplace(0, b): sd = b√2
            w = jax.random.laplace(k, shape, jnp.float32) * b
            out[name] = w.astype(jnp.bfloat16).astype(jnp.float32)
        else:
            out[name] = jnp.ones(shape, jnp.float32)
    return out


def draw(seed: int, index: int, shapes: dict, n_layers: int,
         residual_out=(), device=None):
    """Weights of one group (index 0: the globals, l + 1: layer l) as
    float32 device arrays holding bfloat16 values."""
    key = jax.random.fold_in(seed_key(seed), index)
    spec = tuple(sorted((n, tuple(s), std(n, s, n_layers, residual_out))
                        for n, s in shapes.items()))
    with jax.default_device(device or jax.devices()[0]):
        return _draw(key, spec=spec)


def host_model(seed: int, model, c, device=None):
    """All weights of the model as float32 numpy arrays on the host, for
    the program's packer: (globals, [(layer kind, layer weights)])."""
    glob_shapes, layers = model.shapes(c)
    args = (len(layers), model.RESIDUAL_OUT)
    glob = jax.device_get(draw(seed, 0, glob_shapes, *args, device=device))
    per_layer = [(kind, jax.device_get(draw(seed, i + 1, shp, *args,
                                            device=device)))
                 for i, (kind, shp) in enumerate(layers)]
    return glob, per_layer
