"""Serving runtime — compressed-weight inference, the paper's system.

Pipeline (paper §2.3 "inference", adapted per DESIGN.md §2):
  1. ``build_serve_params`` (host, offline): quantize every policy-selected
     weight to int8 per-channel, build ONE model-wide dictionary over the
     quantized byte streams, blocked-encode each tensor. Weights now live
     in HBM compressed.
  2. ``prefill`` / ``decode_step`` (device, jit): each layer decodes its
     weights on demand inside the forward graph via the fused
     decode→dequant→matmul megakernel (kernels/fused_decode_matmul.py),
     so peak HBM = compressed model + KV cache + one VMEM tile — the
     paper's "decompress layer by layer", tile-granular on TPU.  MoE
     expert stacks — where ~all of a QMoE-class model's bytes live — go
     through the grouped expert megakernel (one launch per stacked
     expert weight, expert grid axis; ``ops.grouped_decode_dequant_
     matmul``), extending the memory invariant to experts: peak HBM =
     compressed experts + capacity-gathered activations + one VMEM tile,
     with dense expert weights never materialized on any device.
     ``generate`` runs the whole decode phase under one jitted
     ``lax.scan`` so the kernel executes back-to-back with no per-token
     host sync or retrace.

Weight modes mirror the paper's evaluation triple:
  dense → "llama3.2-*", quant → "* Quantized", compressed → "* Compressed".

Request-level serving lives one layer up: ``serve.scheduler.Engine``
(continuous batching over a paged KV pool, ``submit``/``step``/``drain``)
reuses this module's ``prefill``/``decode_step`` closures and the shared
``sample_tokens`` rule, so its per-request outputs are bitwise-equal to
one-shot ``generate`` runs of the same prompts.  ``make_serve_fns`` and
``generate`` stay as the fixed-batch compatibility surface; both accept a
``ServeContext`` (serve/context.py) in place of the deprecated loose
``lut=``/``mesh=`` kwargs.

Resilience (core/integrity.py + serve/resilience.py): ``build_serve_
params`` also emits a per-plane integrity manifest (CRC32 over every
codes/literals/nlit/scale/zero plane, the model-wide LUT and the table)
stored on ``ServeState.manifest``.  The integrity invariant: when serving
runs with verification on (``launch/serve --verify fast|full``, or a
``ResiliencePolicy(verify=...)``), no compressed plane is decoded before
``verify_serve_state`` has re-hashed it against that manifest and the
device-side ``check_invariants`` pass (codes index inside the LUT, nlit
within literal capacity, finite affines) has run — corrupted leaves are
named and quarantined (``IntegrityError``), never silently decoded.
Runtime faults degrade instead of dying: ``ResilientEngine`` retries a
bounded number of times, then descends the ladder fused megakernel →
``impl='unfused'`` two-step → ``impl='materialize'`` dense einsum →
refuse-with-diagnostic, ticking ``resilience.FALLBACK_COUNTS`` per rung.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import os
import warnings
from concurrent.futures import ThreadPoolExecutor
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (CompressionPolicy, QuantConfig, build_lut,
                        find_frequent_sequences,
                        quantize_linear)
from repro.core.compressed import (PackedLinear, QuantLinear,
                                   TiledPackedLinear, encode_tiled_planes,
                                   pad_literals)
from repro.core import blocked_codec as bcdc
from repro.core.codec import GramIndex
from repro.core.blocked_codec import DEFAULT_BLOCK_WEIGHTS
from repro.models import lm as LM
from repro.models import encdec as ED
from repro.models import layers as L


@dataclasses.dataclass
class ServeState:
    params: Any
    lut: Optional[jax.Array]
    table: Optional[dict]
    mode: str
    stats: dict
    # per-plane integrity manifest (core/integrity.py) recorded at pack
    # time; verify_serve_state re-hashes against it before serving.
    manifest: Optional[dict] = None


def _iter_weight_paths(params):
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    for path, leaf in flat:
        yield jax.tree_util.keystr(path), leaf


def _column_tiled(policy: CompressionPolicy, name: str, shape2) -> bool:
    """Stored as 2D-TP TiledPackedLinear column tiles?  Expert stacks never
    are: they stay stacked PackedLinear so the grouped expert megakernel
    keeps them compressed-resident under expert parallelism."""
    return (policy.tiles > 1 and shape2[-1] % policy.tiles == 0
            and "experts" not in name)


def _unstack(lead: tuple, *stacks):
    """(S, ...) stacks over a leaf's S = prod(lead) sub-tensors → ``lead +
    (...)``; with no lead dims the single sub-tensor's own shape."""
    return [a.reshape(lead + a.shape[1:]) for a in stacks]


def tile_shards(name: str, model_shards: int) -> tuple:
    """The (out, in) shard counts a weight's fused tiles must divide:
    out-features split over the model axis, except for expert stacks,
    whose expert axis is what splits (expert parallelism)."""
    return (1, 1) if "experts" in name else (model_shards, 1)


def build_serve_params(params: Any, policy: CompressionPolicy,
                       *, qcfg: QuantConfig | None = None,
                       table: dict | None = None,
                       block_weights: int | None = None,
                       model_shards: int = 1,
                       manifest: bool = True) -> ServeState:
    """Host-side conversion dense → quant/compressed per policy.

    Stacked (scanned) leaves keep their leading layer/expert dims: each
    sub-tensor is quantized per-channel and encoded separately, then the
    planes are re-stacked (uniform lit_cap across the stack).

    ``model_shards``: intended model-axis size of the serving mesh — the
    fused tile choice then divides the per-shard out dim so sharded
    serving dispatches to the shard-mapped fused megakernel instead of
    falling back to the two-step path (see ``ops.decode_dequant_matmul``).
    Stacked expert planes are not divided: expert parallelism splits the
    expert axis, so each device runs the grouped expert megakernel over
    whole expert weights.  ``policy.tiles > 1`` stores eligible weights as
    TiledPackedLinear column tiles (2D-TP resident storage, §Perf D2),
    also tile-major — except expert stacks, which stay stacked
    PackedLinear (grouped-kernel eligible).

    ``manifest=True`` (default) records the per-plane integrity manifest
    (``core.integrity.build_manifest``) on the returned state so
    ``verify_serve_state`` can prove the artifact unchanged at load/boot.
    """
    qcfg = qcfg or QuantConfig(bits=policy.bits, granularity="per_channel")
    bw = block_weights or policy.block_weights
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)

    # Pass 1: decide actions; quantize selected tensors; gather byte streams.
    actions, quantized = [], {}
    streams = []
    for i, (path, leaf) in enumerate(flat):
        name = jax.tree_util.keystr(path)
        if not hasattr(leaf, "shape") or leaf.ndim < 2:
            actions.append("dense")
            continue
        shape2 = leaf.shape[-2:]         # per-layer dense shape
        act = policy.action(name, shape2)
        if (act == "compressed" and not _column_tiled(policy, name, shape2)
                and bcdc.choose_fused_tiles(
                    shape2, bw, shards=tile_shards(name, model_shards))
                is None):
            # No kernel-blockable tile (deepseek's 10944-wide dense FFN):
            # compressed planes could only serve through the two-step path
            # that writes the dense bytes to HBM, so store it quant-only.
            act = "quant"
        actions.append(act)
        if act in ("quant", "compressed"):
            stacked = leaf.reshape((-1,) + shape2)
            qls = [quantize_linear(stacked[j], qcfg)
                   for j in range(stacked.shape[0])]
            quantized[i] = qls
            if act == "compressed":
                streams.extend(np.asarray(q.values, dtype=np.uint8)
                               for q in qls)

    # Pass 2: one model-wide dictionary (paper: single table per model).
    if table is None and streams:
        table = find_frequent_sequences(streams, max_codes=65535)
    lut = index = None
    if table is not None:
        lut = jnp.asarray(build_lut(table))  # empty table → 1 zero row
        index = GramIndex(table)

    # Pass 3: build containers.  Sub-tensors encode on a thread pool: the
    # numpy lookups release the GIL.
    np_lut = None if lut is None else np.asarray(lut)
    new_leaves = []
    n_bytes = {"dense": 0, "quant": 0, "compressed": 0}
    with ThreadPoolExecutor(max_workers=os.cpu_count() or 1) as encode_pool:
        for i, (path, leaf) in enumerate(flat):
            act = actions[i]
            if act == "dense":
                new_leaves.append(leaf)
                if hasattr(leaf, "nbytes"):
                    n_bytes["dense"] += int(leaf.nbytes)
                continue
            qls = quantized[i]
            lead = leaf.shape[:-2]
            if act == "quant":
                vals = jnp.stack([q.values for q in qls]).reshape(
                    lead + leaf.shape[-2:]).astype(jnp.uint8)
                sc = jnp.stack([q.scale for q in qls]).reshape(
                    lead + (leaf.shape[-2], 1))
                zr = jnp.stack([q.zero for q in qls]).reshape(
                    lead + (leaf.shape[-2], 1))
                new_leaves.append(QuantLinear(vals, sc, zr))
                n_bytes["quant"] += int(vals.nbytes + sc.nbytes + zr.nbytes)
            elif _column_tiled(policy, jax.tree_util.keystr(path),
                               leaf.shape[-2:]):
                # 2D-TP column-tile storage, fused tile-major per tile.
                per = list(encode_pool.map(lambda q: encode_tiled_planes(
                    np.asarray(q.values, dtype=np.uint8), index, np_lut,
                    policy.tiles, block_weights=bw, tile="auto",
                    shards=(model_shards, 1)), qls))
                tn, tk = per[0][1], per[0][2]
                cap = max(bc.literals.shape[1]
                          for bcs, _, _ in per for bc in bcs)

                def stackplane(f):
                    return jnp.stack([jnp.stack([f(bc) for bc in bcs])
                                      for bcs, _, _ in per])

                codes = stackplane(lambda bc: bc.codes)
                lits = stackplane(lambda bc: pad_literals(bc.literals, cap))
                nlit = stackplane(lambda bc: bc.nlit)
                sc = jnp.stack([q.scale for q in qls])
                zr = jnp.stack([q.zero for q in qls])
                codes, lits, nlit, sc, zr = _unstack(lead, codes, lits, nlit,
                                                     sc, zr)
                tl = TiledPackedLinear(codes, lits, nlit, sc, zr,
                                       shape=tuple(leaf.shape[-2:]),
                                       tile_n=tn, tile_k=tk)
                new_leaves.append(tl)
                n_bytes["compressed"] += tl.payload_nbytes + int(
                    sc.nbytes + zr.nbytes)
            else:
                # Tile-major layout, so serving hits the fused
                # decode→dequant→matmul megakernel.  The tile choice divides
                # the per-``model_shards`` out dim so the shard-mapped fused
                # path stays reachable on the target mesh.
                tn, tk, _ = bcdc.choose_fused_tiles(
                    leaf.shape[-2:], bw,
                    shards=tile_shards(jax.tree_util.keystr(path), model_shards))
                # encode each sub-tensor with a uniform literal capacity
                bcs = list(encode_pool.map(lambda q: bcdc.encode_blocked_tiled(
                    np.asarray(q.values, dtype=np.uint8), index,
                    lut=np_lut, tile_n=tn, tile_k=tk, block_weights=bw), qls))
                cap = max(bc.literals.shape[1] for bc in bcs)
                codes = jnp.stack([bc.codes for bc in bcs])
                lits = jnp.stack([pad_literals(bc.literals, cap) for bc in bcs])
                nlit = jnp.stack([bc.nlit for bc in bcs])
                sc = jnp.stack([q.scale for q in qls])
                zr = jnp.stack([q.zero for q in qls])
                codes, lits, nlit, sc, zr = _unstack(lead, codes, lits, nlit,
                                                     sc, zr)
                from repro.sharding.partition import (clean_keystr,
                                                      is_row_parallel)
                pl = PackedLinear(codes, lits, nlit, sc, zr,
                                  shape=tuple(leaf.shape[-2:]),
                                  row_parallel=is_row_parallel(
                                      clean_keystr(jax.tree_util.keystr(path))),
                                  tile_n=tn, tile_k=tk)
                new_leaves.append(pl)
                n_bytes["compressed"] += pl.payload_nbytes + int(
                    sc.nbytes + zr.nbytes)

    params_out = treedef.unflatten(new_leaves)
    if lut is not None:
        n_bytes["compressed"] += int(lut.nbytes)
    mode = policy.mode
    mf = None
    if manifest:
        from repro.core import integrity
        mf = integrity.build_manifest(params_out, lut, table)
    return ServeState(params=params_out, lut=lut, table=table, mode=mode,
                      stats=n_bytes, manifest=mf)


# ---------------------------------------------------------------------------
# jit-able step functions.
# ---------------------------------------------------------------------------

# Python-body execution counts of the serve closures — a body runs once per
# jit (re)trace, so tests can assert the decode loop traces once instead of
# once per token.  Keyed by closure name.
TRACE_COUNTS = collections.Counter()


def make_serve_fns(cfg=None, *, jit: bool = True, mesh=None, ctx=None):
    """Returns (prefill, decode_step) for serving.

    prefill(params, lut, tokens_or_embeds, caches) -> (last_logits, caches)
    decode_step(params, lut, token, caches, pos) -> (logits, caches)

    By default the closures come back jit-compiled and cached per config
    (``lut``/``params`` are ordinary traced arguments), so repeated callers
    — ``examples/serve_batched.py``, ``benchmarks/latency.py`` — never
    re-trace per call.  ``jit=False`` returns the raw closures for callers
    that apply their own pjit shardings (launch/dryrun) or embed the step
    in a larger traced computation (the ``generate`` scan loop / the
    scheduler's ``generate_step``).

    ``ctx``: a ``ServeContext`` — the preferred way to carry (cfg, mesh);
    passing ``mesh`` loosely still works but is deprecated (warns).  A
    concrete mesh is made visible (``partition.active_mesh``) at trace
    time, so in-graph constraints and the shard-mapped fused
    decode→dequant→matmul paths see it; the jit cache keys on (cfg, mesh),
    so mesh-less and sharded closures never share a stale trace.

    ``decode_step``'s ``pos`` is a scalar offset shared by the whole batch
    *or* a per-row (B,) vector (the continuous-batching paged view — see
    ``models.layers._kv_write`` / ``serve.scheduler``).
    """
    if ctx is not None:
        cfg = ctx.cfg if cfg is None else cfg
        mesh = ctx.mesh
        if getattr(ctx, "residency", None) is not None:
            # Tiered expert residency: the returned closures run each step
            # through the ResidencyManager's fetch/replay protocol (always
            # jitted inside — see serve/residency.py).
            from repro.serve import residency as _res
            return _res.make_tiered_serve_fns(
                ctx if cfg is ctx.cfg else ctx.with_cfg(cfg))
    elif mesh is not None:
        _warn_loose_kwargs("make_serve_fns")
    if jit:
        return _jitted_serve_fns(cfg, mesh)
    return _raw_serve_fns(cfg)


def _mesh_ctx(mesh):
    from repro.sharding.partition import active_mesh
    return active_mesh(mesh) if mesh is not None else contextlib.nullcontext()


@functools.lru_cache(maxsize=None)
def _jitted_serve_fns(cfg, mesh=None):
    prefill, decode_step = _raw_serve_fns(cfg)

    def wrap(fn):
        @jax.jit
        def wrapped(*args):
            with _mesh_ctx(mesh):   # trace-time: constraints see the mesh
                return fn(*args)
        return wrapped

    if mesh is None:
        return jax.jit(prefill), jax.jit(decode_step)
    return wrap(prefill), wrap(decode_step)


def _raw_serve_fns(cfg, routing: bool = False):
    """``routing=True`` (MoE only): prefill/decode_step additionally return
    the per-layer top-k expert ids — (L_moe, n_tok, k) int32 — so the
    tiered residency manager can plan fetches from the step it just ran
    (serve/residency.py)."""
    fam = cfg.family
    if routing and fam == "encdec":
        raise ValueError("routing capture is not supported for encdec")

    def _last_logits(params, hidden, lut=None):
        """LM head on the final position only — prefill never materializes
        (B, T, V) logits (25 GiB/dev at 32k×100k-vocab; §Perf iteration 3)."""
        head = params.get("lm_head", params.get("embed"))
        logits = L.linear(hidden[:, -1:], head, lut)
        if cfg.logits_softcap:
            c = cfg.logits_softcap
            logits = jnp.tanh(logits / c) * c
        return logits[:, 0]

    if fam == "encdec":
        def prefill(params, lut, batch, caches):
            TRACE_COUNTS["prefill"] += 1
            hidden, new_caches = ED.forward(
                params, cfg, batch["enc_embeds"], batch["tokens"],
                caches=caches, pos=0, lut=lut, return_hidden=True)
            return _last_logits(params, hidden, lut), new_caches

        def decode_step(params, lut, token, caches, pos):
            TRACE_COUNTS["decode_step"] += 1
            logits, new_caches = ED.decode_step(params, cfg, token, caches,
                                                pos, lut=lut)
            return logits[:, -1], new_caches
        return prefill, decode_step

    if routing:
        def prefill_r(params, lut, batch, caches):
            TRACE_COUNTS["prefill"] += 1
            hidden, new_caches, _, eids = LM.forward(
                params, cfg, batch.get("tokens"),
                embeds=batch.get("embeds"), caches=caches, pos=0, lut=lut,
                return_hidden=True, return_routing=True)
            return _last_logits(params, hidden, lut), new_caches, eids

        def decode_step_r(params, lut, token, caches, pos):
            TRACE_COUNTS["decode_step"] += 1
            logits, new_caches, _, eids = LM.forward(
                params, cfg, token, caches=caches, pos=pos, lut=lut,
                return_routing=True)
            return logits[:, -1], new_caches, eids

        return prefill_r, decode_step_r

    def prefill(params, lut, batch, caches):
        TRACE_COUNTS["prefill"] += 1
        hidden, new_caches, _ = LM.forward(
            params, cfg, batch.get("tokens"), embeds=batch.get("embeds"),
            caches=caches, pos=0, lut=lut, return_hidden=True)
        return _last_logits(params, hidden, lut), new_caches

    def decode_step(params, lut, token, caches, pos):
        TRACE_COUNTS["decode_step"] += 1
        logits, new_caches, _ = LM.forward(params, cfg, token, caches=caches,
                                           pos=pos, lut=lut)
        return logits[:, -1], new_caches

    return prefill, decode_step


def sample_tokens(logits, temperature, key=None):
    """The one next-token rule for every decode path.

    The legacy one-shot loop (``_decode_loop``) and the continuous-batching
    ``scheduler._generate_step`` both sample through here, so greedy /
    temperature sampling cannot drift between the two — single-request
    parity between them is *bitwise*.

    logits: (B, V).  Three modes:
      * ``key=None`` or scalar ``temperature <= 0`` → greedy argmax.
      * scalar ``temperature`` + key → ``categorical(key, logits / T)``
        (identical to the historical in-loop sampling).
      * array ``temperature`` (B,) + per-row keys (B, 2) → vmapped
        per-row categorical; rows with temperature 0 take the argmax
        result exactly (bitwise equal to the greedy path).
    Returns (B,) token ids.
    """
    greedy = jnp.argmax(logits, axis=-1)
    if key is None:
        return greedy
    if jnp.ndim(temperature) == 0:
        if isinstance(temperature, (int, float)) and temperature <= 0:
            return greedy
        return jax.random.categorical(key, logits / temperature, axis=-1)
    temp = jnp.asarray(temperature, jnp.float32)
    sampled = jax.vmap(jax.random.categorical)(
        key, logits / jnp.maximum(temp, 1e-6)[:, None])
    return jnp.where(temp > 0, sampled, greedy)


@partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _decode_loop(cfg, steps: int, temperature: float, mesh,
                 params, lut, tok0, caches, pos0, key):
    """``steps`` decode steps under one ``lax.scan`` — a single trace and a
    single device program for the whole decode phase, instead of one
    host-synced dispatch (and, un-jitted, one retrace) per token.  ``mesh``
    (static, hashable) scopes the trace under ``active_mesh`` so sharded
    decode runs the same single program through the shard-mapped fused
    kernel paths."""
    TRACE_COUNTS["decode_loop"] += 1
    _, decode_step = _raw_serve_fns(cfg)
    sample = temperature > 0 and key is not None

    def step(carry, _):
        tok, caches, pos, key = carry
        logits, caches = decode_step(params, lut, tok, caches, pos)
        if sample:
            key, sub = jax.random.split(key)
            nxt = sample_tokens(logits, temperature,
                                sub)[:, None].astype(tok.dtype)
        else:
            nxt = sample_tokens(logits, 0.0)[:, None].astype(tok.dtype)
        return (nxt, caches, pos + 1, key), nxt

    init = (tok0, caches, jnp.asarray(pos0, jnp.int32), key)
    with _mesh_ctx(mesh):
        _, toks = jax.lax.scan(step, init, None, length=steps)
    return jnp.swapaxes(toks[..., 0], 0, 1)        # (steps, B, 1) -> (B, steps)


def _warn_loose_kwargs(caller: str):
    warnings.warn(
        f"{caller}: loose lut=/mesh= kwargs are deprecated — pass "
        "ctx=ServeContext(cfg, mesh=..., lut=...) (repro.serve.context) "
        "instead", DeprecationWarning, stacklevel=3)


def generate(params, cfg, tokens, *, ctx=None, lut=None, max_new: int = 16,
             max_len: int | None = None, temperature: float = 0.0,
             key=None, embeds=None, mesh=None):
    """One-shot greedy/sampled generation (examples + accuracy benchmarks).

    Prefill runs once under jit; the decode phase is a single jitted
    ``lax.scan`` over ``decode_step`` (see ``_decode_loop``), so compressed
    layers hit the fused decode→dequant→matmul kernel back-to-back with no
    per-token host sync or retrace.  Serve sharded by passing a mesh (via
    ``ctx``): the same single-trace loop then dispatches through the
    shard-mapped fused paths (see ``ops.decode_dequant_matmul``).

    ``ctx``: a ``ServeContext`` carrying (cfg, mesh, lut) — the preferred
    spelling; the loose ``lut=``/``mesh=`` kwargs remain as a deprecated
    compatibility path (they warn).  For request-level serving — admission
    into a running batch, per-request completion — use
    ``serve.scheduler.Engine`` instead; this entry point stays the
    fixed-batch reference the scheduler's outputs are bitwise-checked
    against.
    """
    if ctx is not None:
        cfg = ctx.cfg if cfg is None else cfg
        lut, mesh = ctx.lut, ctx.mesh
        if getattr(ctx, "residency", None) is not None:
            # Tiered expert residency: a host-stepped decode loop through
            # the ResidencyManager (bitwise-equal to this scan loop — the
            # per-step jitted program is the same computation; see
            # serve/residency.py and tests/test_residency.py).
            from repro.serve import residency as _res
            return _res.tiered_generate(
                params, cfg, tokens, ctx=ctx, max_new=max_new,
                max_len=max_len, temperature=temperature, key=key,
                embeds=embeds)
    elif lut is not None or mesh is not None:
        _warn_loose_kwargs("generate")
    if max_new <= 0:
        return tokens
    b, t0 = tokens.shape
    extra = embeds.shape[1] if embeds is not None else 0
    max_len = max_len or (t0 + extra + max_new)
    caches = LM.init_caches(cfg, b, max_len)
    from repro.serve.context import ServeContext
    prefill, _ = make_serve_fns(ctx=ServeContext(cfg=cfg, mesh=mesh, lut=lut))
    logits, caches = prefill(params, lut,
                             {"tokens": tokens, "embeds": embeds}, caches)
    tok0 = sample_tokens(logits, 0.0)[:, None].astype(tokens.dtype)
    if max_new <= 1:
        return jnp.concatenate([tokens, tok0], axis=1)
    toks = _decode_loop(cfg, max_new - 1, float(temperature), mesh,
                        params, lut, tok0, caches, t0 + extra, key)
    return jnp.concatenate([tokens, tok0, toks.astype(tokens.dtype)], axis=1)
