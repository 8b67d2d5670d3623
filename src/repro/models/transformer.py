"""Unified model assembly for every assigned architecture family.

A model is a stack of pre-norm blocks; each block has a *mixer* chosen by
``cfg.layer_pattern`` ("global" / "local" attention, "recurrent" RG-LRU,
"ssd" Mamba-2) and an FFN (dense SwiGLU or MoE).  Layers are stacked in
*pattern cycles* and iterated with ``lax.scan`` over stacked parameters so
deep configs (94 layers) lower quickly; the remainder layers (when
``n_layers % len(pattern) != 0``) run unrolled.

Encoder-decoder (seamless-m4t) adds a bidirectional encoder over
precomputed frontend embeddings and cross-attention in every decoder block.
VLM/audio prefix embeddings are concatenated ahead of token embeddings
(the modality frontend is a stub per the assignment).
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


from repro.util import scan_or_unroll as _scan
from repro.models import layers as L
from repro.models.base import (ParamDef, build, fan_in_scale, retype_defs,
                               stack_defs)
from repro.models.config import ModelConfig
from repro.models.sharding import maybe_shard


# ------------------------------ block defs ---------------------------------


def _mixer_defs(cfg: ModelConfig, mixer: str, model_ax: int) -> dict:
    if mixer in ("global", "local"):
        return L.attention_defs(cfg, model_ax)
    if mixer == "recurrent":
        return L.rglru_defs(cfg, model_ax)
    if mixer == "ssd":
        return L.ssd_defs(cfg, model_ax)
    raise ValueError(mixer)


def _ffn_defs(cfg: ModelConfig, model_ax: int) -> dict | None:
    if cfg.n_experts:
        return L.moe_defs(cfg, model_ax)
    if cfg.d_ff:
        return L.mlp_defs(cfg, model_ax)
    return None  # pure-SSM archs have no separate FFN


def block_defs(cfg: ModelConfig, mixer: str, model_ax: int,
               cross: bool = False) -> dict:
    d = {"norm1": L.rmsnorm_defs(cfg.d_model),
         "mixer": _mixer_defs(cfg, mixer, model_ax)}
    ffn = _ffn_defs(cfg, model_ax)
    if ffn is not None:
        d["norm2"] = L.rmsnorm_defs(cfg.d_model)
        d["ffn"] = ffn
    if cross:
        d["norm_x"] = L.rmsnorm_defs(cfg.d_model)
        d["cross"] = L.attention_defs(cfg, model_ax)
    return d


def model_defs(cfg: ModelConfig, model_ax: int = 1) -> dict:
    pattern = cfg.layer_pattern
    n_groups = cfg.n_layers // len(pattern)
    rem = cfg.n_layers % len(pattern)
    defs: dict[str, Any] = {
        "embed": L.embedding_defs(cfg, model_ax),
        "final_norm": L.rmsnorm_defs(cfg.d_model),
        "layers": [stack_defs(block_defs(cfg, m, model_ax,
                                         cross=cfg.is_encdec), n_groups)
                   for m in pattern],
        "tail": [block_defs(cfg, pattern[j], model_ax,
                            cross=cfg.is_encdec) for j in range(rem)],
    }
    if not cfg.tie_embeddings:
        v = L.padded_vocab(cfg, model_ax)
        defs["lm_head"] = ParamDef(
            (cfg.d_model, v), P("data", L._shard_if(v, model_ax)),
            scale=fan_in_scale(cfg.d_model))
    if cfg.is_encdec:
        defs["encoder"] = {
            "layers": stack_defs(block_defs(cfg, "global", model_ax),
                                 cfg.encoder_layers),
            "final_norm": L.rmsnorm_defs(cfg.d_model),
        }
    return retype_defs(defs, cfg.dtype)


def init_params(cfg: ModelConfig, rng: jax.Array, model_ax: int = 1):
    return build(model_defs(cfg, model_ax), "init", rng)


def param_shapes(cfg: ModelConfig, model_ax: int = 1):
    return build(model_defs(cfg, model_ax), "shape")


def param_specs(cfg: ModelConfig, model_ax: int = 1):
    return build(model_defs(cfg, model_ax), "spec")


# ------------------------------ forward ------------------------------------


def _block_apply(cfg: ModelConfig, p: dict, h: jax.Array, mixer: str,
                 positions: jax.Array, enc_out: jax.Array | None = None,
                 enc_positions: jax.Array | None = None,
                 ) -> tuple[jax.Array, jax.Array]:
    aux = jnp.zeros((), jnp.float32)
    window = cfg.window if mixer == "local" else None
    hn = L.rmsnorm(p["norm1"], h)
    if mixer in ("global", "local"):
        h = h + L.attention_apply(cfg, p["mixer"], hn, positions,
                                  causal=True, window=window)
    elif mixer == "recurrent":
        h = h + L.rglru_apply(cfg, p["mixer"], hn)
    elif mixer == "ssd":
        h = h + L.ssd_apply(cfg, p["mixer"], hn)
    if enc_out is not None and "cross" in p:
        hx = L.rmsnorm(p["norm_x"], h)
        h = h + _cross_attention(cfg, p["cross"], hx, enc_out,
                                 positions, enc_positions)
    if "ffn" in p:
        hf = L.rmsnorm(p["norm2"], h)
        if cfg.n_experts:
            out, a = L.moe_apply(cfg, p["ffn"], hf)
            h = h + out
            aux = aux + a
        else:
            h = L.mlp_apply(p["ffn"], hf, residual=h)
    return h, aux


def _cross_attention(cfg, p, x, enc_out, positions, enc_positions):
    b, s, _ = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    se = enc_out.shape[1]
    q = (x @ p["wq"]).reshape(b, s, hq, hd)
    k = (enc_out @ p["wk"]).reshape(b, se, hkv, hd)
    v = (enc_out @ p["wv"]).reshape(b, se, hkv, hd)
    out = L.mha(q, k, v, causal=False)
    return out.reshape(b, s, hq * hd) @ p["wo"]


def _encoder_apply(cfg: ModelConfig, params: dict, embeds: jax.Array):
    enc = params["encoder"]
    b, se, _ = embeds.shape
    positions = jnp.broadcast_to(jnp.arange(se), (b, se))
    h = embeds

    def step(carry, p):
        h = carry
        hn = L.rmsnorm(p["norm1"], h)
        h = h + L.attention_apply(cfg, p["mixer"], hn, positions,
                                  causal=False)
        hf = L.rmsnorm(p["norm2"], h)
        h = L.mlp_apply(p["ffn"], hf, residual=h)
        return h, None

    h, _ = _scan(step, h, enc["layers"])
    return L.rmsnorm(enc["final_norm"], h), positions


def forward(cfg: ModelConfig, params: dict, tokens: jax.Array,
            prefix_embeds: jax.Array | None = None,
            enc_embeds: jax.Array | None = None) -> tuple[jax.Array,
                                                          jax.Array]:
    """Full-sequence forward.  Returns (hidden (B,S,D), aux_loss)."""
    emb = params["embed"]["embedding"]
    h = jnp.take(emb, tokens, axis=0) * (cfg.d_model ** 0.5)
    if prefix_embeds is not None:
        h = jnp.concatenate([prefix_embeds.astype(h.dtype), h], axis=1)
    b, s, _ = h.shape
    h = maybe_shard(h, P("data", None, None))
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))

    enc_out = enc_positions = None
    if cfg.is_encdec:
        assert enc_embeds is not None
        enc_out, enc_positions = _encoder_apply(cfg, params, enc_embeds)

    pattern = cfg.layer_pattern
    aux_total = jnp.zeros((), jnp.float32)

    def cycle(h, cycle_params):
        aux = jnp.zeros((), jnp.float32)
        for j, mixer in enumerate(pattern):
            h, a = _block_apply(cfg, cycle_params[j], h, mixer, positions,
                                enc_out, enc_positions)
            aux = aux + a
        return h, aux

    if cfg.remat in ("block", "full"):
        cycle = jax.checkpoint(cycle)
    elif cfg.remat == "dots":
        # §Perf lever: save matmul outputs, recompute elementwise only —
        # removes most of the remat FLOP waste at modest activation memory
        cycle = jax.checkpoint(
            cycle,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)

    def scan_step(carry, cycle_params):
        h, aux = carry
        h, a = cycle(h, cycle_params)
        return (h, aux + a), None

    n_groups = cfg.n_layers // len(pattern)
    if n_groups:
        (h, aux_total), _ = _scan(scan_step, (h, aux_total),
                                         params["layers"])
    for j, p in enumerate(params["tail"]):
        h, a = _block_apply(cfg, p, h, pattern[j], positions, enc_out,
                            enc_positions)
        aux_total = aux_total + a
    h = L.rmsnorm(params["final_norm"], h)
    return h, aux_total


def logits_fn(cfg: ModelConfig, params: dict, h: jax.Array) -> jax.Array:
    if cfg.tie_embeddings:
        logits = h @ params["embed"]["embedding"].T
    else:
        logits = h @ params["lm_head"]
    logits = maybe_shard(logits, P("data", None, "model"))
    if cfg.final_logit_cap is not None:
        logits = cfg.final_logit_cap * jnp.tanh(
            logits.astype(jnp.float32) / cfg.final_logit_cap)
    return logits


def loss_fn(cfg: ModelConfig, params: dict, batch: dict,
            model_ax: int = 1) -> tuple[jax.Array, dict]:
    """Cross-entropy LM loss.  batch: tokens, labels (+ modality extras)."""
    h, aux = forward(cfg, params, batch["tokens"],
                     prefix_embeds=batch.get("prefix_embeds"),
                     enc_embeds=batch.get("enc_embeds"))
    if batch.get("prefix_embeds") is not None:
        h = h[:, batch["prefix_embeds"].shape[1]:, :]  # loss on text only
    logits = logits_fn(cfg, params, h).astype(jnp.float32)
    labels = batch["labels"]
    mask = (labels >= 0).astype(jnp.float32)
    labels = jnp.maximum(labels, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None],
                               axis=-1)[..., 0]
    nll = (logz - gold) * mask
    loss = jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1.0)
    total = loss + 0.01 * aux
    return total, {"loss": loss, "aux": aux,
                   "tokens": jnp.sum(mask)}


def prefill(cfg: ModelConfig, params: dict, tokens: jax.Array,
            max_seq: int, prefix_embeds: jax.Array | None = None,
            enc_embeds: jax.Array | None = None,
            full_kv: bool = False,
            logits_at: jax.Array | int | None = None):
    """Full-sequence forward that also writes the decode caches.

    Returns (last_logits (B, V), cache).  Caches are sized to ``max_seq``
    (global attention) / ``window`` (local) / O(1) (ssd, recurrent).

    Serving plumbing: ``full_kv=True`` keeps windowed layers' K/V in the
    full position-indexed layout (the paged cache scatters it into pages
    and masks the window at decode time); ``logits_at`` returns the
    logits of that sequence position instead of the last one — bucketed
    prefill right-pads a prompt to its bucket, so the "last real token"
    sits at ``true_len - 1``, not at ``bucket - 1``.
    """
    emb = params["embed"]["embedding"]
    h = jnp.take(emb, tokens, axis=0) * (cfg.d_model ** 0.5)
    if prefix_embeds is not None:
        h = jnp.concatenate([prefix_embeds.astype(h.dtype), h], axis=1)
    b, s, _ = h.shape
    h = maybe_shard(h, P("data", None, None))
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))

    enc_out = enc_positions = None
    if cfg.is_encdec:
        assert enc_embeds is not None
        enc_out, enc_positions = _encoder_apply(cfg, params, enc_embeds)

    pattern = cfg.layer_pattern

    def block_prefill(p, h, mixer):
        hn = L.rmsnorm(p["norm1"], h)
        window = cfg.window if mixer == "local" else None
        if mixer in ("global", "local"):
            out, cache = L.attention_apply(
                cfg, p["mixer"], hn, positions, causal=True, window=window,
                return_cache=max_seq, full_cache=full_kv)
        elif mixer == "recurrent":
            out, cache = L.rglru_apply(cfg, p["mixer"], hn,
                                       return_cache=True)
        elif mixer == "ssd":
            out, cache = L.ssd_apply(cfg, p["mixer"], hn, return_cache=True)
        h = h + out
        if enc_out is not None and "cross" in p:
            hx = L.rmsnorm(p["norm_x"], h)
            h = h + _cross_attention(cfg, p["cross"], hx, enc_out,
                                     positions, enc_positions)
            se = enc_out.shape[1]
            hkv, hd = cfg.n_kv_heads, cfg.head_dim
            cache = dict(cache)
            cache["cross_k"] = (enc_out @ p["cross"]["wk"]).reshape(
                b, se, hkv, hd).astype(cfg.dtype)
            cache["cross_v"] = (enc_out @ p["cross"]["wv"]).reshape(
                b, se, hkv, hd).astype(cfg.dtype)
        if "ffn" in p:
            hf = L.rmsnorm(p["norm2"], h)
            if cfg.n_experts:
                out, _ = L.moe_apply(cfg, p["ffn"], hf)
                h = h + out
            else:
                h = L.mlp_apply(p["ffn"], hf, residual=h)
        return h, cache

    def scan_step(h, cycle_params):
        caches = []
        for j, mixer in enumerate(pattern):
            h, c = block_prefill(cycle_params[j], h, mixer)
            caches.append(c)
        return h, caches

    n_groups = cfg.n_layers // len(pattern)
    if n_groups:
        h, layer_caches = _scan(scan_step, h, params["layers"])
    else:
        layer_caches = [jax.tree.map(lambda d: None, {})] * 0
    tail_caches = []
    for j, p in enumerate(params["tail"]):
        h, c = block_prefill(p, h, pattern[j])
        tail_caches.append(c)
    h = L.rmsnorm(params["final_norm"], h)
    if logits_at is None:
        h_last = h[:, -1:, :]
    else:
        h_last = jax.lax.dynamic_slice_in_dim(h, logits_at, 1, axis=1)
    logits = logits_fn(cfg, params, h_last)[:, 0, :]
    return logits, {"layers": layer_caches if n_groups else [],
                    "tail": tail_caches}


# ------------------------------ decoding -----------------------------------


def cache_defs(cfg: ModelConfig, batch: int, max_seq: int,
               model_ax: int = 1, enc_seq: int = 0) -> dict:
    """Decode-state tree matching the layer structure."""
    pattern = cfg.layer_pattern
    n_groups = cfg.n_layers // len(pattern)
    rem = cfg.n_layers % len(pattern)

    def one(mixer: str) -> dict:
        if mixer == "global":
            return L.attention_cache_defs(cfg, batch, max_seq, model_ax,
                                          None)
        if mixer == "local":
            return L.attention_cache_defs(cfg, batch, max_seq, model_ax,
                                          cfg.window)
        if mixer == "recurrent":
            return L.rglru_cache_defs(cfg, batch, model_ax)
        if mixer == "ssd":
            return L.ssd_cache_defs(cfg, batch, model_ax)
        raise ValueError(mixer)

    def with_cross(d: dict) -> dict:
        if cfg.is_encdec:
            hkv, hd = cfg.n_kv_heads, cfg.head_dim
            d = dict(d)
            d["cross_k"] = ParamDef((batch, enc_seq, hkv, hd),
                                    P("data", None, None, None),
                                    init="zeros", dtype=cfg.dtype)
            d["cross_v"] = ParamDef((batch, enc_seq, hkv, hd),
                                    P("data", None, None, None),
                                    init="zeros", dtype=cfg.dtype)
        return d

    return {
        "layers": [stack_defs(with_cross(one(m)), n_groups)
                   for m in pattern],
        "tail": [with_cross(one(pattern[j])) for j in range(rem)],
    }


def init_cache(cfg: ModelConfig, batch: int, max_seq: int,
               model_ax: int = 1, enc_seq: int = 0):
    return build(cache_defs(cfg, batch, max_seq, model_ax, enc_seq),
                 "init", jax.random.PRNGKey(0))


def cache_specs(cfg: ModelConfig, batch: int, max_seq: int,
                model_ax: int = 1, enc_seq: int = 0):
    return build(cache_defs(cfg, batch, max_seq, model_ax, enc_seq), "spec")


def cache_shapes(cfg: ModelConfig, batch: int, max_seq: int,
                 model_ax: int = 1, enc_seq: int = 0):
    return build(cache_defs(cfg, batch, max_seq, model_ax, enc_seq),
                 "shape")


def _block_decode(cfg: ModelConfig, p: dict, h: jax.Array, mixer: str,
                  cache: dict, pos: jax.Array,
                  attn_step=None) -> tuple[jax.Array, dict]:
    """One block's decode step.

    ``attn_step`` swaps the attention-layer implementation: it receives
    ``(params, hn, cache, pos, window)`` and returns ``(out, new cache
    entries)``.  The default is the dense per-request cache
    (``L.attention_decode``); the serving subsystem passes the paged
    flash-decode step (``serve.kv_cache.make_paged_attn_step``).  The
    recurrent / SSD / FFN structure is shared by both paths.
    """
    hn = L.rmsnorm(p["norm1"], h)
    new_cache = dict(cache)
    if mixer in ("global", "local"):
        window = cfg.window if mixer == "local" else None
        if attn_step is None:
            attn_cache = {"k": cache["k"], "v": cache["v"]}
            out, attn_new = L.attention_decode(cfg, p["mixer"], hn,
                                               attn_cache, pos,
                                               window=window)
        else:
            out, attn_new = attn_step(p["mixer"], hn, cache, pos, window)
        h = h + out
        new_cache.update(attn_new)
    elif mixer == "recurrent":
        out, rc = L.rglru_decode(cfg, p["mixer"], hn,
                                 {"conv": cache["conv"], "h": cache["h"]})
        h = h + out
        new_cache.update(rc)
    elif mixer == "ssd":
        out, sc = L.ssd_decode(cfg, p["mixer"], hn,
                               {"conv": cache["conv"],
                                "state": cache["state"]})
        h = h + out
        new_cache.update(sc)
    if "cross" in p and "cross_k" in cache:
        hx = L.rmsnorm(p["norm_x"], h)
        h = h + _cross_decode(cfg, p["cross"], hx, cache["cross_k"],
                              cache["cross_v"])
    if "ffn" in p:
        hf = L.rmsnorm(p["norm2"], h)
        if cfg.n_experts:
            out, _ = L.moe_apply(cfg, p["ffn"], hf)
            h = h + out
        else:
            h = L.mlp_apply(p["ffn"], hf, residual=h)
    return h, new_cache


def _cross_decode(cfg, p, x, ck, cv):
    b = x.shape[0]
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(b, 1, hq, hd)
    groups = hq // hkv
    qh = q.reshape(b, hkv, groups, hd)
    logits = jnp.einsum("bhgd,blhd->bhgl", qh.astype(jnp.float32),
                        ck.astype(jnp.float32)) * hd ** -0.5
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgl,blhd->bhgd", probs, cv.astype(jnp.float32))
    return out.reshape(b, 1, hq * hd).astype(x.dtype) @ p["wo"]


def decode_step(cfg: ModelConfig, params: dict, token: jax.Array,
                cache: dict, pos: jax.Array,
                attn_step=None) -> tuple[jax.Array, dict]:
    """One decode step.  token: (B,) int32; returns (logits (B, V), cache).

    ``attn_step`` (see :func:`_block_decode`) substitutes the attention
    cache implementation — the paged serving engine threads its
    flash-decode step through here so every non-attention layer reuses
    this exact code path.

    A 2-D ``token`` of shape (B, S) is the multi-token span form
    (speculative verify / chunked prefill): the S tokens occupy
    consecutive positions starting at ``pos``, and logits come back for
    EVERY position, (B, S, V).  Only the attention mixers support spans
    (the rglru/ssd state updates are strictly one-token), so this form
    requires a span-capable ``attn_step``
    (``serve.kv_cache.make_paged_span_step``) and an attention-only
    ``layer_pattern``; the norm/FFN/MoE structure is shape-polymorphic
    and shared verbatim.
    """
    single = token.ndim == 1
    if not single:
        if attn_step is None:
            raise ValueError("multi-token decode_step needs a span-capable "
                             "attn_step (the dense cache is one-token)")
        bad = [m for m in cfg.layer_pattern if m not in ("global", "local")]
        if bad:
            raise ValueError(f"multi-token decode_step is attention-only; "
                             f"layer_pattern has {bad}")
    emb = params["embed"]["embedding"]
    h = jnp.take(emb, token[:, None] if single else token,
                 axis=0) * (cfg.d_model ** 0.5)
    pattern = cfg.layer_pattern

    def scan_step(carry, xs):
        # the stacked caches ride in the carry and each layer's slice is
        # written back in place: as scan outputs they would be a second
        # whole copy of every cache (pools are most of device memory)
        h, caches = carry
        cycle_params, i = xs
        for j, mixer in enumerate(pattern):
            layer = jax.tree.map(lambda c: c[i], caches[j])
            h, nc = _block_decode(cfg, cycle_params[j], h, mixer,
                                  layer, pos, attn_step)
            caches[j] = jax.tree.map(
                lambda c, n: jax.lax.dynamic_update_index_in_dim(
                    c, n.astype(c.dtype), i, 0), caches[j], nc)
        return (h, caches), None

    n_groups = cfg.n_layers // len(pattern)
    if n_groups:
        (h, new_layer_caches), _ = _scan(
            scan_step, (h, list(cache["layers"])),
            (params["layers"], jnp.arange(n_groups)))
    else:
        new_layer_caches = cache["layers"]
    new_tail = []
    for j, p in enumerate(params["tail"]):
        h, nc = _block_decode(cfg, p, h, pattern[j], cache["tail"][j], pos,
                              attn_step)
        new_tail.append(nc)
    h = L.rmsnorm(params["final_norm"], h)
    logits = logits_fn(cfg, params, h)
    if single:
        logits = logits[:, 0, :]
    return logits, {"layers": new_layer_caches, "tail": new_tail}


def prefill_cross_cache(cfg: ModelConfig, params: dict, cache: dict,
                        enc_embeds: jax.Array) -> dict:
    """Encoder-decoder: run the encoder once, fill cross K/V caches."""
    enc_out, _ = _encoder_apply(cfg, params, enc_embeds)
    b, se, _ = enc_out.shape
    hkv, hd = cfg.n_kv_heads, cfg.head_dim

    def fill(group_params, group_cache):
        k = (enc_out @ group_params["cross"]["wk"]).reshape(b, se, hkv, hd)
        v = (enc_out @ group_params["cross"]["wv"]).reshape(b, se, hkv, hd)
        gc = dict(group_cache)
        gc["cross_k"] = k.astype(cfg.dtype)
        gc["cross_v"] = v.astype(cfg.dtype)
        return gc

    new = {"layers": [], "tail": []}
    for gp, gc in zip(params["layers"], cache["layers"]):
        new["layers"].append(_fill_stacked(cfg, gp, gc, enc_out))
    for p, c in zip(params["tail"], cache["tail"]):
        new["tail"].append(fill(p, c))
    return new


def _fill_stacked(cfg, gp, gc, enc_out):
    b, se, _ = enc_out.shape
    hkv, hd = cfg.n_kv_heads, cfg.head_dim

    def one(wk, wv):
        k = (enc_out @ wk).reshape(b, se, hkv, hd).astype(cfg.dtype)
        v = (enc_out @ wv).reshape(b, se, hkv, hd).astype(cfg.dtype)
        return k, v

    ks, vs = jax.vmap(one)(gp["cross"]["wk"], gp["cross"]["wv"])
    out = dict(gc)
    out["cross_k"] = ks
    out["cross_v"] = vs
    return out
