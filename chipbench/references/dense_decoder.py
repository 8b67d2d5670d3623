"""Plain reference of a dense pre-norm decoder (granite as published),
written from the configuration file alone.

Per layer: ``h += r Wo attn(rope(Wq n1(h)), rope(Wk n1(h)), Wv n1(h))``,
then ``h += r Wdown(silu(Wgate n2(h)) * Wup n2(h))``, where ``r`` is
``residual_multiplier``, ``n`` is RMSNorm (``rms_norm_eps``, a learned
scale), attention is causal GQA scaled by ``attention_multiplier``, and
RoPE rotates the two halves of each head (``rope_theta``).  Embeddings
are scaled by ``embedding_multiplier``; the logits are the final-normed
state times the tied embedding, over ``vocab_size`` rows, divided by
``logits_scaling``.

It runs in float32 with every matrix product at ``HIGHEST`` precision,
one layer at a time and one sequence at a time, so that only one
layer's weights are ever upcast.  ``mode="int8"`` is the control: every
projection and the LM head multiply int8 activations (absmax per row)
by int8 weights (absmax per output column), and the attention scores
and the weighting of values multiply int8 operands (absmax over the
contracted axis), all with int32 accumulation.
The reference takes the weight tree the benchmark drew, never anything
the program made, and divides out the factors by which the benchmark
folded the published scalars into it (``weights.folds``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import weights

HI = jax.lax.Precision.HIGHEST
BLOCK = 512          # sequences are padded to a multiple of this


def _quant(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.round(x / scale).astype(jnp.int8), scale


def _mm(x, w, mode):
    """(n, k) @ (k, m) in float32, or as int8 x int8 -> int32."""
    if mode == "f32":
        return jnp.matmul(x, w.astype(jnp.float32), precision=HI)
    xq, xs = _quant(x, 1)
    wq, ws = _quant(w.astype(jnp.float32), 0)
    acc = jax.lax.dot_general(xq, wq, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * xs * ws


def _ein(spec, a, b, a_axis, b_axis, mode):
    """An attention product in float32, or as int8 x int8 -> int32 with
    absmax scales over each operand's contracted axis."""
    if mode == "f32":
        return jnp.einsum(spec, a, b, precision=HI)
    aq, as_ = _quant(a, a_axis)
    bq, bs = _quant(b, b_axis)
    acc = jnp.einsum(spec, aq, bq, preferred_element_type=jnp.int32)
    ins, out = spec.split("->")
    sa, sb = ins.split(",")
    scale = jnp.einsum(f"{sa},{sb}->{out}", as_, bs)
    return acc.astype(jnp.float32) * scale


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("c", "mode"))
def _layer(h, lw, *, c, mode):
    """One decoder layer over a padded (L, d) sequence; causal, so the
    padding after the real tokens changes nothing before it."""
    n, d = h.shape
    hq, hkv = c["heads"], c["kv_heads"]
    hd = d // hq
    g = hq // hkv
    pos = jnp.arange(n)
    x = _rms(h, lw["norm1"], c["eps"])
    q = _rope(_mm(x, lw["wq"], mode).reshape(n, hq, hd), pos, c["theta"])
    k = _rope(_mm(x, lw["wk"], mode).reshape(n, hkv, hd), pos, c["theta"])
    v = _mm(x, lw["wv"], mode).reshape(n, hkv, hd)
    q = q.reshape(n, hkv, g, hd)
    outs = []
    for s in range(0, n, BLOCK):           # query blocks keep scores small
        qb = q[s:s + BLOCK]
        sc = _ein("qhgd,khd->hgqk", qb, k, -1, -1, mode) * c["attn"]
        mask = (s + jnp.arange(qb.shape[0]))[:, None] >= pos[None, :]
        sc = jnp.where(mask, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(_ein("hgqk,khd->qhgd", p, v, -1, 0, mode))
    o = jnp.concatenate(outs, 0).reshape(n, hq * hd)
    h = h + c["resid"] * _mm(o, lw["wo"], mode)
    x = _rms(h, lw["norm2"], c["eps"])
    u = jax.nn.silu(_mm(x, lw["w_gate"], mode)) * _mm(x, lw["w_up"], mode)
    return h + c["resid"] * _mm(u, lw["w_down"], mode)


@functools.partial(jax.jit, static_argnames=("c", "mode", "m"))
def _gaps(h_ref, h_other, norm, emb, nxt, lo, *, c, mode, m):
    """Per position j of the ``m`` positions from ``lo`` of a padded
    sequence: how far below the reference's best logit lies the
    reference's logit of token ``nxt[j]`` (the token that followed),
    and of the token that ``h_other`` (computed in ``mode``) puts
    first."""
    h_ref = jax.lax.dynamic_slice_in_dim(h_ref, lo, m)
    h_other = jax.lax.dynamic_slice_in_dim(h_other, lo, m)
    nxt = jax.lax.dynamic_slice_in_dim(nxt, lo, m)
    w = emb[:c["vocab"]].T.astype(jnp.float32) / c["fold_emb"]
    norm = norm.astype(jnp.float32) / c["fold_final"]
    ref = _mm(_rms(h_ref, norm, c["eps"]), w, "f32") / c["logits_div"]
    other = _mm(_rms(h_other, norm, c["eps"]), w, mode) / c["logits_div"]
    best = jnp.max(ref, axis=-1)
    rows = jnp.arange(ref.shape[0])
    served = best - ref[rows, nxt]
    pick = best - ref[rows, jnp.argmax(other, axis=-1)]
    return served, pick


@functools.partial(jax.jit, static_argnames=("c",))
def _embed(emb, toks, *, c):
    return emb[toks].astype(jnp.float32) / c["fold_emb"] * c["emb_mult"]


def _consts(config: dict):
    f = weights.folds(config)
    return tuple(sorted({
        "fold_emb": f["embedding"], "fold_final": f["final_norm"],
        "fold_q": f["wq"], "fold_o": f["wo"], "fold_down": f["w_down"],
        "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "eps": float(config["rms_norm_eps"]),
        "theta": float(config["rope_theta"]),
        "attn": float(config["attention_multiplier"]),
        "resid": float(config["residual_multiplier"]),
        "emb_mult": float(config["embedding_multiplier"]),
        "logits_div": float(config["logits_scaling"]),
        "vocab": int(config["vocab_size"]),
    }.items()))


class _C(dict):
    """Hashable constants for the jitted functions' static argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def _layer_weights(c, params, i: int) -> dict:
    """Layer ``i``'s published weights, in float32, from the stacked tree
    the benchmark drew."""
    stack = params["layers"][0]
    fold = {"wq": c["fold_q"], "wo": c["fold_o"], "w_down": c["fold_down"]}
    lw = {"norm1": stack["norm1"]["scale"][i],
          "norm2": stack["norm2"]["scale"][i],
          **{k: stack["mixer"][k][i] for k in ("wq", "wk", "wv", "wo")},
          **{k: stack["ffn"][k][i] for k in ("w_gate", "w_up", "w_down")}}
    return {k: w.astype(jnp.float32) / fold.get(k, 1.0)
            for k, w in lw.items()}


def _hidden(c, params, seqs, mode):
    emb = params["embed"]["embedding"]
    hs = []
    for s in seqs:
        n = -(-len(s) // BLOCK) * BLOCK
        toks = np.zeros(n, np.int32)
        toks[:len(s)] = s
        hs.append(_embed(emb, jnp.asarray(toks), c=c))
    for i in range(len(params["layers"][0]["norm1"]["scale"])):
        lw = _layer_weights(c, params, i)
        hs = [_layer(h, lw, c=c, mode=mode) for h in hs]
    return hs


def gaps(config: dict, params, seqs, starts, control: str | None = None):
    """For each sequence, at every position from ``starts[r]`` on, how
    far the reference's logit of the token there lies below the
    reference's best (``served``), and, with ``control`` (``"int8"``),
    how far the reference's logit of the token that the control
    computation puts first lies below it (``control``).  Host arrays,
    one entry per scored position."""
    c = _C(_consts(config))
    ref = _hidden(c, params, seqs, "f32")
    other = _hidden(c, params, seqs, control) if control else ref
    out = []
    for h, o, s, st in zip(ref, other, seqs, starts):
        n = len(s) - st                     # positions scored
        m = min(-(-n // BLOCK) * BLOCK, h.shape[0])
        lo = min(st - 1, h.shape[0] - m)
        nxt = np.zeros(h.shape[0], np.int32)
        nxt[:len(s) - 1] = s[1:]
        served, pick = _gaps(h, o, params["final_norm"]["scale"],
                             params["embed"]["embedding"], jnp.asarray(nxt),
                             jnp.int32(lo), c=c, mode=control or "f32", m=m)
        sl = slice(st - 1 - lo, st - 1 - lo + n)
        out.append({"served": np.asarray(served)[sl],
                    "control": np.asarray(pick)[sl]})
    return out
