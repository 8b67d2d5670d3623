"""Model layers: each module declares ParamDefs and provides apply fns.

Sharding philosophy (paper §3.3 mapped to a TPU mesh, DESIGN.md §3):
weights are the "large buffer" for LM layers, so they are sharded over the
``model`` axis (K-partitioning: heads / ffn / experts / vocab) while
activations are sharded over ``data`` (XY-partitioning: batch/sequence).
``model_ax`` (the model-axis size) is threaded through the def builders so
dims that don't divide are replicated instead of mis-sharded.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ops, ref
from repro.models.base import ParamDef, fan_in_scale
from repro.models.config import ModelConfig
from repro.models.sharding import maybe_shard, on_mesh, translate_spec


def _shard_if(dim: int, model_ax: int, axis: str = "model"):
    return axis if model_ax > 1 and dim % model_ax == 0 else None


# =========================== norms & embeddings ===========================


def rmsnorm_defs(d: int) -> dict:
    return {"scale": ParamDef((d,), P(None), init="ones")}


def rmsnorm(params: dict, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) *
            params["scale"].astype(jnp.float32)).astype(x.dtype)


def embedding_defs(cfg: ModelConfig, model_ax: int) -> dict:
    v = padded_vocab(cfg, model_ax)
    return {"embedding": ParamDef((v, cfg.d_model),
                                  P(_shard_if(v, model_ax), "data"),
                                  scale=cfg.d_model ** -0.5)}


def padded_vocab(cfg: ModelConfig, model_ax: int = 16) -> int:
    mult = max(model_ax, 1) * 16  # lane-align shards
    return ((cfg.vocab + mult - 1) // mult) * mult


# ================================ RoPE =====================================


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq   # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin],
        axis=-1).astype(x.dtype)


# ============================ attention (GQA) ==============================


def attention_defs(cfg: ModelConfig, model_ax: int) -> dict:
    d, hd = cfg.d_model, cfg.head_dim
    hq, hkv = cfg.n_heads, cfg.n_kv_heads
    sq = _shard_if(hq * hd, model_ax) if hq % model_ax == 0 or \
        model_ax <= 1 else None
    skv = "model" if model_ax > 1 and hkv % model_ax == 0 else None
    s = fan_in_scale(d)
    # FSDP: the non-"model" dim of every weight is sharded over "data"
    # (ZeRO-3 storage; GSPMD all-gathers per layer and reduce-scatters
    # gradients automatically).
    return {
        "wq": ParamDef((d, hq * hd), P("data", sq), scale=s),
        "wk": ParamDef((d, hkv * hd), P("data", skv), scale=s),
        "wv": ParamDef((d, hkv * hd), P("data", skv), scale=s),
        "wo": ParamDef((hq * hd, d), P(sq, "data"),
                       scale=fan_in_scale(hq * hd)),
    }


def mha(q: jax.Array, k: jax.Array, v: jax.Array, **kw) -> jax.Array:
    """``ops.attention`` over (B, S, H, D) activations.  On a mesh it runs
    per device, on each device's share of the batch ("data") and of the
    KV heads ("model"): GSPMD cannot partition the compiled attention
    kernel.  A dim its mesh axes do not divide stays whole."""
    fn = functools.partial(ops.attention, **kw)
    if not on_mesh():
        return fn(q, k, v)
    mesh = jax.sharding.get_abstract_mesh()
    used: set = set()

    def fit(axis, n: int):
        names = axis if isinstance(axis, tuple) else (axis,)
        names = tuple(a for a in names
                      if a in mesh.axis_names and a not in used)
        if not names or n % math.prod(mesh.shape[a] for a in names):
            return None
        used.update(names)
        return names

    batch_ax, head_ax = translate_spec(P("data", "model"))
    spec = P(fit(batch_ax, q.shape[0]), None, fit(head_ax, k.shape[2]))
    return jax.shard_map(fn, in_specs=(spec, spec, spec), out_specs=spec,
                         check_vma=False)(q, k, v)


def attention_apply(cfg: ModelConfig, params: dict, x: jax.Array,
                    positions: jax.Array, *, causal: bool = True,
                    window: int | None = None,
                    return_cache: bool = False,
                    full_cache: bool = False):
    """Full-sequence attention.  x: (B, S, D).

    ``full_cache=True`` forces the returned K/V cache into the full
    position-indexed layout even for windowed (local) layers — the paged
    serving path stores every layer's KV in pages and applies the window
    as a mask at decode time, so it cannot use the ring-buffer layout.
    """
    b, s, d = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    if ops.fused_ops_enabled():
        # one weight-stationary pass: x streams from HBM once for all
        # three projections (docs/fusion.md)
        q, k, v = ops.qkv_fused(x, params["wq"], params["wk"],
                                params["wv"])
        q = q.reshape(b, s, hq, hd)
        k = k.reshape(b, s, hkv, hd)
        v = v.reshape(b, s, hkv, hd)
    else:
        q = ops.linear(x, params["wq"]).reshape(b, s, hq, hd)
        k = ops.linear(x, params["wk"]).reshape(b, s, hkv, hd)
        v = ops.linear(x, params["wv"]).reshape(b, s, hkv, hd)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    out = mha(q, k, v, causal=causal, window=window,
              logit_cap=cfg.attn_logit_cap)
    out = ops.linear(out.reshape(b, s, hq * hd), params["wo"])
    if not return_cache:
        return out
    cache_len = return_cache if isinstance(return_cache, int) and \
        return_cache is not True else s
    cache_dtype = cfg.kv_cache_dtype or cfg.dtype
    if window is not None and not full_cache:
        # ring buffer: slot p % L holds position p; keep the last L
        length = min(window, cache_len)
        keep = min(length, s)
        last = jnp.arange(s - keep, s)
        ck = jnp.zeros((b, length, hkv, hd), cache_dtype)
        cv = jnp.zeros((b, length, hkv, hd), cache_dtype)
        ck = ck.at[:, last % length].set(k[:, last].astype(cache_dtype))
        cv = cv.at[:, last % length].set(v[:, last].astype(cache_dtype))
        return out, {"k": ck, "v": cv}
    pad = cache_len - s
    ck = jnp.pad(k.astype(cache_dtype), ((0, 0), (0, pad), (0, 0), (0, 0)))
    cv = jnp.pad(v.astype(cache_dtype), ((0, 0), (0, pad), (0, 0), (0, 0)))
    return out, {"k": ck, "v": cv}


def qkv_span_proj(cfg: ModelConfig, params: dict, x: jax.Array,
                  positions: jax.Array):
    """Q/K/V projection + rope for a span of S consecutive tokens — the
    single definition shared by the dense decode path
    (:func:`attention_decode`, S=1), the paged decode path
    (``serve.kv_cache.make_paged_attn_step``) and the multi-token
    verify/chunked-prefill path (``make_paged_span_step``), so they can
    never drift apart.  x: (B, S, D); positions: (B, S).
    Returns q (B, S, Hq, D), k/v (B, S, Hkv, D)."""
    b, s, _ = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    if ops.fused_ops_enabled():
        # fused path falls back to the three ops.linear calls itself
        # when the weights are QuantizedTensors (w8 semantics intact)
        q, k, v = ops.qkv_fused(x.reshape(b * s, -1), params["wq"],
                                params["wk"], params["wv"])
        q, k, v = (q.reshape(b, s, hq, hd), k.reshape(b, s, hkv, hd),
                   v.reshape(b, s, hkv, hd))
    else:
        # ops.linear (not a bare @): quantized params carry
        # QuantizedTensor projection weights, which linear dispatches to
        # the w8 kernel / dequant oracle (docs/quantization.md)
        q = ops.linear(x, params["wq"]).reshape(b, s, hq, hd)
        k = ops.linear(x, params["wk"]).reshape(b, s, hkv, hd)
        v = ops.linear(x, params["wv"]).reshape(b, s, hkv, hd)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def qkv_decode_proj(cfg: ModelConfig, params: dict, x: jax.Array,
                    positions: jax.Array):
    """One-token wrapper over :func:`qkv_span_proj`.  x: (B, D);
    positions: (B, 1).  Returns q (B, Hq, D), k/v (B, Hkv, D)."""
    q, k, v = qkv_span_proj(cfg, params, x[:, None, :], positions)
    return q[:, 0], k[:, 0], v[:, 0]


def attention_decode(cfg: ModelConfig, params: dict, x: jax.Array,
                     cache: dict, pos: jax.Array, *,
                     window: int | None = None) -> tuple[jax.Array, dict]:
    """One-token step.  x: (B, 1, D); cache {k,v}: (B, L, hkv, hd) where
    L = window (ring buffer) for local layers else max seq."""
    b, _, d = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    posv = jnp.full((b, 1), pos, jnp.int32)
    q, k, v = qkv_decode_proj(cfg, params, x[:, 0], posv)
    q, k, v = q[:, None], k[:, None], v[:, None]

    length = cache["k"].shape[1]
    slot = pos % length if window is not None else pos
    ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                      (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                      (0, slot, 0, 0))

    slots = jnp.arange(length)
    if window is not None:
        kpos = pos - (pos - slots) % length          # ring-buffer positions
        valid = (kpos >= 0) & (kpos <= pos) & (kpos > pos - window)
    else:
        kpos = slots
        valid = kpos <= pos

    groups = hq // hkv
    qh = q.reshape(b, hkv, groups, hd)               # (B, hkv, G, D)
    logits = jnp.einsum("bhgd,blhd->bhgl", qh.astype(jnp.float32),
                        ck.astype(jnp.float32)) * hd ** -0.5
    if cfg.attn_logit_cap is not None:
        logits = cfg.attn_logit_cap * jnp.tanh(logits / cfg.attn_logit_cap)
    logits = jnp.where(valid[None, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgl,blhd->bhgd", probs, cv.astype(jnp.float32))
    out = out.reshape(b, 1, hq * hd).astype(x.dtype)
    return ops.linear(out, params["wo"]), {"k": ck, "v": cv}


def attention_cache_defs(cfg: ModelConfig, batch: int, max_seq: int,
                         model_ax: int, window: int | None) -> dict:
    hkv, hd = cfg.n_kv_heads, cfg.head_dim
    cache_dtype = cfg.kv_cache_dtype or cfg.dtype
    length = min(window, max_seq) if window is not None else max_seq
    if model_ax > 1 and hkv % model_ax == 0:
        spec = P("data", None, "model", None)       # head-sharded KV
    elif model_ax > 1 and length % model_ax == 0:
        # GQA/MQA: too few kv heads to split -> shard the SEQUENCE dim
        # (flash-decode style); XLA inserts the partial-softmax reductions.
        spec = P("data", "model", None, None)
    else:
        spec = P("data", None, None, None)
    return {"k": ParamDef((batch, length, hkv, hd), spec, init="zeros",
                          dtype=cache_dtype),
            "v": ParamDef((batch, length, hkv, hd), spec, init="zeros",
                          dtype=cache_dtype)}


# ========================== dense MLP (SwiGLU) =============================


def mlp_defs(cfg: ModelConfig, model_ax: int) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    sh = _shard_if(f, model_ax)
    defs = {
        "w_up": ParamDef((d, f), P("data", sh), scale=fan_in_scale(d)),
        "w_down": ParamDef((f, d), P(sh, "data"), scale=fan_in_scale(f)),
    }
    if cfg.mlp_kind == "swiglu":
        defs["w_gate"] = ParamDef((d, f), P("data", sh),
                                  scale=fan_in_scale(d))
    return defs


def mlp_apply(params: dict, x: jax.Array,
              residual: jax.Array | None = None) -> jax.Array:
    """The MLP block.  ``residual`` (when given) is added to the output
    — callers pass the skip connection so the fused path can absorb the
    add into the down-projection's epilogue.

    With fused ops enabled (``ops.fused_ops`` — the serving engines'
    ``fuse`` flag), the whole chain runs as epilogue-fused GEMMs under
    the ``"matmul_fused"`` schedule key (``"matmul_w8"`` for quantized
    weights): activation, SwiGLU gating multiply and residual add all
    happen on the VMEM-resident output tile, eliminating their HBM
    round-trips (docs/fusion.md).  Otherwise the per-op chain below
    runs — ops.linear is a plain matmul unless blocked linears are
    enabled (training with tc.blocked_linear / REPRO_BLOCKED_LINEAR),
    in which case fwd AND bwd run the tuned Pallas GEMM kernels.
    """
    if ops.fused_ops_enabled():
        if "w_gate" in params:  # SwiGLU
            g = ops.matmul_fused(x, params["w_gate"], act="silu")
            u = ops.matmul_fused(x, params["w_up"], mul=g)
        else:  # plain GELU MLP
            u = ops.matmul_fused(x, params["w_up"], act="gelu")
        return ops.matmul_fused(u, params["w_down"], residual=residual)
    u = ops.linear(x, params["w_up"]).astype(jnp.float32)
    if "w_gate" in params:  # SwiGLU
        g = jax.nn.silu(ops.linear(x, params["w_gate"]).astype(jnp.float32))
        u = g * u
    else:  # plain GELU MLP (granite-34b, seamless encoder/decoder)
        u = jax.nn.gelu(u)
    out = ops.linear(u.astype(x.dtype), params["w_down"])
    return out if residual is None else residual + out


# ============================ MoE (top-k) ==================================


def moe_defs(cfg: ModelConfig, model_ax: int) -> dict:
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    se = _shard_if(e, model_ax)   # expert parallelism over the model axis
    return {
        "router": ParamDef((d, e), P("data", None), scale=fan_in_scale(d)),
        "w_gate": ParamDef((e, d, f), P(se, "data", None),
                           scale=fan_in_scale(d)),
        "w_up": ParamDef((e, d, f), P(se, "data", None),
                         scale=fan_in_scale(d)),
        "w_down": ParamDef((e, f, d), P(se, None, "data"),
                           scale=fan_in_scale(f)),
    }


def moe_apply(cfg: ModelConfig, params: dict, x: jax.Array,
              ) -> tuple[jax.Array, jax.Array]:
    """Capacity-based top-k MoE.

    On a mesh with a model axis, dispatch runs under ``shard_map``: each
    shard routes ITS tokens locally (sort/scatter with no collectives) and
    exchanges expert slices with one explicit all-to-all over the model
    axis (+ inverse for combine) — the §Perf iteration that replaced the
    global-argsort dispatch whose GSPMD lowering moved ~170 TB/step
    (EXPERIMENTS.md §Perf it. 3).  Off-mesh (or when token counts don't
    split) the reference dense dispatch below runs instead; it is also the
    correctness oracle for the shard_map path.

    Paper §3.3 view: experts are the large KB -> partition them, route the
    small token blocks.  Returns (output, aux_load_balance_loss).
    """
    from repro.models.sharding import get_axis_mapping, on_mesh
    if on_mesh():
        mapping = get_axis_mapping()
        if mapping.get("model"):
            try:
                return _moe_apply_shardmap(cfg, params, x, mapping)
            except _ShardMapUnavailable:
                pass
    return _moe_apply_ref(cfg, params, x)


class _ShardMapUnavailable(Exception):
    pass


def _moe_apply_shardmap(cfg: ModelConfig, params: dict, x: jax.Array,
                        mapping: dict) -> tuple[jax.Array, jax.Array]:
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size <= 1:
        raise _ShardMapUnavailable()
    ma = mapping["model"]
    da = mapping.get("data")
    da = da if isinstance(da, tuple) else ((da,) if da else ())
    m_size = mesh.shape[ma]
    b, s, d = x.shape
    d_size = 1
    for a in da:
        d_size *= mesh.shape[a]
    t_loc = (b // d_size if b % d_size == 0 else b) * s
    e, k = cfg.n_experts, cfg.experts_per_token
    if t_loc % m_size or e % m_size or b % max(d_size, 1):
        raise _ShardMapUnavailable()

    x_spec = P(da if da else None, None, None)
    w_specs = {kk: translate_spec(v) for kk, v in {
        "router": P(None, None),
        "w_gate": P("model", None, None),
        "w_up": P("model", None, None),
        "w_down": P("model", None, None)}.items()}

    def local(xs, router, w_gate, w_up, w_down):
        bl, sl, _ = xs.shape
        tl = bl * sl
        tm = tl // m_size
        midx = jax.lax.axis_index(ma)
        xf = xs.reshape(tl, d)
        mine = jax.lax.dynamic_slice(xf, (midx * tm, 0), (tm, d))

        logits = (mine @ router).astype(jnp.float32)          # (tm, E)
        probs = jax.nn.softmax(logits, axis=-1)
        topw, topi = jax.lax.top_k(probs, k)
        topw = topw / jnp.sum(topw, axis=-1, keepdims=True)
        density = jnp.mean(jax.nn.one_hot(topi[:, 0], e), axis=0)
        aux = jnp.sum(density * jnp.mean(probs, axis=0)) * e
        axes = (ma,) + tuple(da)
        aux = jax.lax.pmean(aux, axes)

        cap = int(math.ceil(tm * k / e * cfg.capacity_factor))
        cap = max(8, ((cap + 7) // 8) * 8)
        e_flat = topi.reshape(-1)
        order = jnp.argsort(e_flat)
        e_sort = e_flat[order]
        w_sort = topw.reshape(-1)[order]
        tok_sort = order // k
        pos = jnp.arange(tm * k) - jnp.searchsorted(e_sort, e_sort,
                                                    side="left")
        keep = pos < cap
        slot = jnp.where(keep, e_sort * cap + pos, e * cap)
        buf = jnp.zeros((e * cap + 1, d), xs.dtype).at[slot].set(
            mine[tok_sort] * keep[:, None].astype(xs.dtype))
        buf = buf[:-1].reshape(e, cap, d)

        # expert-parallel exchange: send each model-peer its expert slice
        buf = jax.lax.all_to_all(buf, ma, split_axis=0, concat_axis=1,
                                 tiled=True)      # (e/M, cap*M, d)
        g = jnp.einsum("ecd,edf->ecf", buf, w_gate,
                       preferred_element_type=jnp.float32)
        u = jnp.einsum("ecd,edf->ecf", buf, w_up,
                       preferred_element_type=jnp.float32)
        h = (jax.nn.silu(g) * u).astype(xs.dtype)
        out_e = jnp.einsum("ecf,efd->ecd", h, w_down,
                           preferred_element_type=jnp.float32
                           ).astype(xs.dtype)
        out_e = jax.lax.all_to_all(out_e, ma, split_axis=1, concat_axis=0,
                                   tiled=True)    # (e, cap, d)

        flat = jnp.concatenate([out_e.reshape(e * cap, d),
                                jnp.zeros((1, d), xs.dtype)], axis=0)
        gathered = flat[slot] * (w_sort * keep)[:, None].astype(xs.dtype)
        mine_out = jnp.zeros((tm, d), xs.dtype).at[tok_sort].add(gathered)
        # reassemble the model-replicated activation row
        out = jax.lax.all_gather(mine_out, ma, axis=0,
                                 tiled=True)       # (tl, d)
        return out.reshape(bl, sl, d), aux

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(x_spec, w_specs["router"], w_specs["w_gate"],
                  w_specs["w_up"], w_specs["w_down"]),
        out_specs=(x_spec, P()),
        check_vma=False)
    return fn(x, params["router"], params["w_gate"], params["w_up"],
              params["w_down"])


def _moe_apply_ref(cfg: ModelConfig, params: dict, x: jax.Array,
                   ) -> tuple[jax.Array, jax.Array]:
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.experts_per_token
    t = b * s
    xf = x.reshape(t, d)

    logits = (xf @ params["router"]).astype(jnp.float32)      # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, k)                       # (T, k)
    topw = topw / jnp.sum(topw, axis=-1, keepdims=True)

    # load-balance aux loss (Switch-style)
    density = jnp.mean(jax.nn.one_hot(topi[:, 0], e), axis=0)
    density_proxy = jnp.mean(probs, axis=0)
    aux = jnp.sum(density * density_proxy) * e

    cap = int(math.ceil(t * k / e * cfg.capacity_factor))
    cap = max(8, ((cap + 7) // 8) * 8)

    e_flat = topi.reshape(-1)                                  # (T*k,)
    w_flat = topw.reshape(-1)
    order = jnp.argsort(e_flat)
    e_sort = e_flat[order]
    w_sort = w_flat[order]
    tok_sort = order // k
    pos = jnp.arange(t * k) - jnp.searchsorted(e_sort, e_sort, side="left")
    keep = pos < cap
    slot = jnp.where(keep, e_sort * cap + pos, e * cap)        # drop slot

    buf = jnp.zeros((e * cap + 1, d), x.dtype).at[slot].set(
        xf[tok_sort] * keep[:, None].astype(x.dtype))
    buf = buf[:-1].reshape(e, cap, d)
    buf = maybe_shard(buf, P("model", None, None))

    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, params["w_gate"],
                               preferred_element_type=jnp.float32))
    u = jnp.einsum("ecd,edf->ecf", buf, params["w_up"],
                   preferred_element_type=jnp.float32)
    h = (g * u).astype(x.dtype)
    out_e = jnp.einsum("ecf,efd->ecd", h, params["w_down"],
                       preferred_element_type=jnp.float32).astype(x.dtype)
    out_e = maybe_shard(out_e, P("model", None, None))

    flat = jnp.concatenate([out_e.reshape(e * cap, d),
                            jnp.zeros((1, d), x.dtype)], axis=0)
    gathered = flat[slot] * (w_sort * keep)[:, None].astype(x.dtype)
    out = jnp.zeros((t, d), x.dtype).at[tok_sort].add(gathered)
    return out.reshape(b, s, d), aux


# ============================ SSD (mamba-2) ================================


def ssd_defs(cfg: ModelConfig, model_ax: int) -> dict:
    d, di, ns = cfg.d_model, cfg.d_inner, cfg.ssm_state
    nh = cfg.ssm_heads
    conv_dim = di + 2 * ns
    proj_out = 2 * di + 2 * ns + nh        # z, x, B, C, dt
    sdi = _shard_if(di, model_ax)
    return {
        "in_proj": ParamDef((d, proj_out), P("data", None),
                            scale=fan_in_scale(d)),
        "conv_w": ParamDef((cfg.conv_width, conv_dim), P(None, None),
                           scale=fan_in_scale(cfg.conv_width)),
        "A_log": ParamDef((nh,), P(None), init="zeros", dtype=jnp.float32),
        "D": ParamDef((nh,), P(None), init="ones", dtype=jnp.float32),
        "dt_bias": ParamDef((nh,), P(None), init="zeros",
                            dtype=jnp.float32),
        "norm_scale": ParamDef((di,), P(sdi), init="ones"),
        "out_proj": ParamDef((di, d), P(sdi, "data"),
                             scale=fan_in_scale(di)),
    }


def _ssd_split(cfg: ModelConfig, proj: jax.Array):
    di, ns, nh = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    z = proj[..., :di]
    xbc = proj[..., di:di + di + 2 * ns]
    dt = proj[..., di + di + 2 * ns:]
    return z, xbc, dt


def ssd_apply(cfg: ModelConfig, params: dict, x: jax.Array,
              return_cache: bool = False):
    """Chunked state-space duality forward (Mamba-2 §6).  x: (B, S, D)."""
    b, s, d = x.shape
    di, ns, nh, hp = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, \
        cfg.ssm_head_dim
    q = min(cfg.ssm_chunk, s)
    if s % q:  # snap to the largest divisor of s (ragged prompts)
        q = max(v for v in range(1, q + 1) if s % v == 0)
    nc = s // q

    proj = x @ params["in_proj"]
    z, xbc_raw, dt = _ssd_split(cfg, proj)
    # causal depthwise conv over time
    xbc = _causal_conv1d(xbc_raw, params["conv_w"])
    xbc = jax.nn.silu(xbc.astype(jnp.float32)).astype(x.dtype)
    xs = xbc[..., :di].reshape(b, s, nh, hp)
    bmat = xbc[..., di:di + ns]                        # (B, S, N), G=1
    cmat = xbc[..., di + ns:]

    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    a = -jnp.exp(params["A_log"])                      # (H,)
    da = dt * a                                        # (B, S, H) log-decay

    # chunk views
    xc = xs.reshape(b, nc, q, nh, hp)
    bc = bmat.reshape(b, nc, q, ns).astype(jnp.float32)
    cc = cmat.reshape(b, nc, q, ns).astype(jnp.float32)
    dac = da.reshape(b, nc, q, nh)
    dtc = dt.reshape(b, nc, q, nh)
    cum = jnp.cumsum(dac, axis=2)                      # (B,Nc,Q,H)

    # intra-chunk (the "quadratic attention-like" branch)
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]   # (B,Nc,Q,Q,H)
    tri = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.where(tri[None, None, :, :, None], jnp.exp(seg), 0.0)
    scores = jnp.einsum("bcqn,bckn->bcqk", cc, bc)
    xdt = xc.astype(jnp.float32) * dtc[..., None]
    y_intra = jnp.einsum("bcqk,bcqkh,bckhp->bcqhp", scores, decay, xdt)

    # chunk-final states, then scan the recurrence across chunks
    decay_to_end = jnp.exp(cum[:, :, -1:, :] - cum)        # (B,Nc,Q,H)
    states = jnp.einsum("bckn,bckh,bckhp->bchpn", bc, decay_to_end, xdt)
    chunk_decay = jnp.exp(cum[:, :, -1, :])                # (B,Nc,H)

    def step(carry, inp):
        st, dec = inp                                      # (B,H,P,N),(B,H)
        new = carry * dec[:, :, None, None] + st
        return new, carry                                  # emit PREV state

    init = jnp.zeros((b, nh, hp, ns), jnp.float32)
    final_state, prev_states = jax.lax.scan(
        step, init, (states.transpose(1, 0, 2, 3, 4),
                     chunk_decay.transpose(1, 0, 2)))
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)     # (B,Nc,H,P,N)

    y_inter = jnp.einsum("bcqn,bchpn,bcqh->bcqhp", cc, prev_states,
                         jnp.exp(cum))
    y = (y_intra + y_inter).reshape(b, s, nh, hp)
    y = y + params["D"][None, None, :, None] * xs.astype(jnp.float32)
    y = y.reshape(b, s, di)
    # gated RMSNorm (mamba-2 norm before out-proj)
    y = y * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(y * y, axis=-1, keepdims=True)
    y = y * jax.lax.rsqrt(var + 1e-6) * \
        params["norm_scale"].astype(jnp.float32)
    out = y.astype(x.dtype) @ params["out_proj"]
    if not return_cache:
        return out
    w_hist = cfg.conv_width - 1
    tail = xbc_raw[:, -w_hist:, :].astype(cfg.dtype)
    if s < w_hist:
        tail = jnp.pad(tail, ((0, 0), (w_hist - s, 0), (0, 0)))
    return out, {"conv": tail, "state": final_state}


def _causal_conv1d(x: jax.Array, w: jax.Array) -> jax.Array:
    """Depthwise causal conv over time.  x: (B,S,C); w: (W,C)."""
    width = w.shape[0]
    pad = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    out = jnp.zeros_like(x, dtype=jnp.float32)
    for i in range(width):
        out = out + pad[:, i:i + x.shape[1], :].astype(jnp.float32) * \
            w[i].astype(jnp.float32)
    return out.astype(x.dtype)


def ssd_cache_defs(cfg: ModelConfig, batch: int, model_ax: int) -> dict:
    di, ns, nh, hp = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, \
        cfg.ssm_head_dim
    conv_dim = di + 2 * ns
    return {
        "conv": ParamDef((batch, cfg.conv_width - 1, conv_dim),
                         P("data", None, None), init="zeros",
                         dtype=cfg.dtype),
        "state": ParamDef((batch, nh, hp, ns), P("data", None, None, None),
                          init="zeros", dtype=jnp.float32),
    }


def ssd_decode(cfg: ModelConfig, params: dict, x: jax.Array,
               cache: dict) -> tuple[jax.Array, dict]:
    """Single-token SSD step: O(1) state update.  x: (B, 1, D)."""
    b = x.shape[0]
    di, ns, nh, hp = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, \
        cfg.ssm_head_dim
    proj = x[:, 0] @ params["in_proj"]                     # (B, P_out)
    z, xbc, dt = _ssd_split(cfg, proj[:, None, :])
    z, xbc, dt = z[:, 0], xbc[:, 0], dt[:, 0]

    # conv cache update
    hist = jnp.concatenate([cache["conv"],
                            xbc[:, None, :].astype(cache["conv"].dtype)],
                           axis=1)                          # (B, W, C)
    w = params["conv_w"]
    conv_out = jnp.sum(hist.astype(jnp.float32) *
                       w.astype(jnp.float32)[None], axis=1)
    xbc = jax.nn.silu(conv_out).astype(x.dtype)
    new_conv = hist[:, 1:, :]

    xs = xbc[:, :di].reshape(b, nh, hp)
    bvec = xbc[:, di:di + ns].astype(jnp.float32)
    cvec = xbc[:, di + ns:].astype(jnp.float32)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    a = -jnp.exp(params["A_log"])
    decay = jnp.exp(dt * a)                                 # (B, H)
    upd = jnp.einsum("bn,bh,bhp->bhpn", bvec, dt, xs.astype(jnp.float32))
    state = cache["state"] * decay[:, :, None, None] + upd
    y = jnp.einsum("bn,bhpn->bhp", cvec, state)
    y = y + params["D"][None, :, None] * xs.astype(jnp.float32)
    y = y.reshape(b, di)
    y = y * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(y * y, axis=-1, keepdims=True)
    y = y * jax.lax.rsqrt(var + 1e-6) * \
        params["norm_scale"].astype(jnp.float32)
    out = (y.astype(x.dtype) @ params["out_proj"])[:, None, :]
    return out, {"conv": new_conv, "state": state}


# ========================= RG-LRU (recurrentgemma) =========================

_LRU_C = 8.0


def rglru_defs(cfg: ModelConfig, model_ax: int) -> dict:
    d, w = cfg.d_model, cfg.lru_width
    sw = _shard_if(w, model_ax)
    return {
        "in_x": ParamDef((d, w), P("data", sw), scale=fan_in_scale(d)),
        "in_gate": ParamDef((d, w), P("data", sw), scale=fan_in_scale(d)),
        "conv_w": ParamDef((cfg.conv_width, w), P(None, sw),
                           scale=fan_in_scale(cfg.conv_width)),
        "w_r": ParamDef((w, w), P("data", sw), scale=fan_in_scale(w)),
        "w_i": ParamDef((w, w), P("data", sw), scale=fan_in_scale(w)),
        "lam": ParamDef((w,), P(sw), init="ones", dtype=jnp.float32),
        "out": ParamDef((w, d), P(sw, "data"), scale=fan_in_scale(w)),
    }


def _rglru_gates(params: dict, xr: jax.Array):
    r = jax.nn.sigmoid((xr @ params["w_r"]).astype(jnp.float32))
    i = jax.nn.sigmoid((xr @ params["w_i"]).astype(jnp.float32))
    log_a = -_LRU_C * jax.nn.softplus(params["lam"]) * r
    a = jnp.exp(log_a)
    gated = jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-12)) * \
        (i * xr.astype(jnp.float32))
    return a, gated


def rglru_apply(cfg: ModelConfig, params: dict, x: jax.Array,
                return_cache: bool = False):
    """Griffin recurrent block: conv1d -> RG-LRU -> GeLU-gate.  x:(B,S,D)."""
    gate = jax.nn.gelu((x @ params["in_gate"]).astype(jnp.float32))
    xr_raw = x @ params["in_x"]
    xr = _causal_conv1d(xr_raw, params["conv_w"])
    a, gated = _rglru_gates(params, xr)

    # linear recurrence h_t = a_t h_{t-1} + b_t via associative scan
    def combine(lhs, rhs):
        a1, b1 = lhs
        a2, b2 = rhs
        return a1 * a2, a2 * b1 + b2

    _, h = jax.lax.associative_scan(combine, (a, gated), axis=1)
    y = (h * gate).astype(x.dtype)
    out = y @ params["out"]
    if not return_cache:
        return out
    w_hist = cfg.conv_width - 1
    s = x.shape[1]
    tail = xr_raw[:, -w_hist:, :].astype(cfg.dtype)
    if s < w_hist:
        tail = jnp.pad(tail, ((0, 0), (w_hist - s, 0), (0, 0)))
    return out, {"conv": tail, "h": h[:, -1, :]}


def rglru_cache_defs(cfg: ModelConfig, batch: int, model_ax: int) -> dict:
    w = cfg.lru_width
    sw = _shard_if(w, model_ax)
    return {
        "conv": ParamDef((batch, cfg.conv_width - 1, w),
                         P("data", None, sw), init="zeros", dtype=cfg.dtype),
        "h": ParamDef((batch, w), P("data", sw), init="zeros",
                      dtype=jnp.float32),
    }


def rglru_decode(cfg: ModelConfig, params: dict, x: jax.Array,
                 cache: dict) -> tuple[jax.Array, dict]:
    gate = jax.nn.gelu((x[:, 0] @ params["in_gate"]).astype(jnp.float32))
    xr = x[:, 0] @ params["in_x"]
    hist = jnp.concatenate([cache["conv"],
                            xr[:, None, :].astype(cache["conv"].dtype)],
                           axis=1)
    conv = jnp.sum(hist.astype(jnp.float32) *
                   params["conv_w"].astype(jnp.float32)[None], axis=1)
    xr = conv.astype(x.dtype)
    a, gated = _rglru_gates(params, xr)
    h = a * cache["h"] + gated
    y = (h * gate).astype(x.dtype) @ params["out"]
    return y[:, None, :], {"conv": hist[:, 1:, :], "h": h}
