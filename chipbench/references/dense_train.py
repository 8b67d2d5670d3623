"""Plain reference of training the dense decoder (granite as published):
the loss, its gradient and AdamW, in float32 with every product at
``HIGHEST`` precision, written from the configuration file alone.

The model is ``dense_decoder``'s, over whole sequences: the published
multipliers, RMSNorm, causal GQA with RoPE, SwiGLU and a tied LM head
over the vocabulary's ``vocab_size`` rows.  The loss is the mean
cross-entropy of each next token.  The parameters are the tree the
benchmark drew, in the program's layout with the published scalars
folded in (``weights.folds``): the loss divides them out, so it is the
published model's, and AdamW moves the parameters as held.

AdamW, from the file's ``train.opt``: the gradient clipped to global
norm ``clip_norm``; moments ``b1``, ``b2`` with bias correction; the
update ``mu_hat / (sqrt(nu_hat) + eps) + weight_decay * p`` times a
learning rate that warms up linearly over ``warmup_steps`` and then
follows a cosine from ``lr`` to ``min_lr_frac * lr`` over
``total_steps``.

The gradient is computed on the process's devices, the batch split
across them and each parameter split along an axis they divide; the
optimizer's state lives on the host's CPU device.  ``mode="fp8"`` is
the control: the operands of every matrix product are rounded to
float8 (e4m3, scaled by their absolute maximum), the gradient passing
straight through the rounding.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from references.dense_decoder import _C, _consts, _rms

HI = jax.lax.Precision.HIGHEST
BLOCK = 512          # query block of the attention


def _fp8(x):
    s = jnp.max(jnp.abs(x)) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    r = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(r - x)


def _ein(spec, a, b, mode):
    if mode == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HI)


def _rope(x, theta):
    """x: (B, S, H, D)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(c, mode, h, lw):
    b, n, d = h.shape
    hq, hkv = c["heads"], c["kv_heads"]
    hd = d // hq
    g = hq // hkv
    x = _rms(h, lw["norm1"]["scale"], c["eps"])
    mix = lw["mixer"]
    q = _rope(_ein("bsd,de->bse", x, mix["wq"] / c["fold_q"], mode)
              .reshape(b, n, hq, hd), c["theta"])
    k = _rope(_ein("bsd,de->bse", x, mix["wk"], mode)
              .reshape(b, n, hkv, hd), c["theta"])
    v = _ein("bsd,de->bse", x, mix["wv"], mode).reshape(b, n, hkv, hd)
    q = q.reshape(b, n, hkv, g, hd)
    pos = jnp.arange(n)
    outs = []
    for s in range(0, n, BLOCK):
        qb = q[:, s:s + BLOCK]
        sc = _ein("bqhgd,bkhd->bhgqk", qb, k, mode) * c["attn"]
        mask = (s + jnp.arange(qb.shape[1]))[:, None] >= pos[None, :]
        p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
        outs.append(_ein("bhgqk,bkhd->bqhgd", p, v, mode))
    o = jnp.concatenate(outs, 1).reshape(b, n, hq * hd)
    h = h + c["resid"] * _ein("bse,ed->bsd", o, mix["wo"] / c["fold_o"],
                              mode)
    x = _rms(h, lw["norm2"]["scale"], c["eps"])
    ffn = lw["ffn"]
    u = jax.nn.silu(_ein("bsd,df->bsf", x, ffn["w_gate"], mode)) \
        * _ein("bsd,df->bsf", x, ffn["w_up"], mode)
    return h + c["resid"] * _ein("bsf,fd->bsd", u,
                                 ffn["w_down"] / c["fold_down"], mode)


def _loss(c, mode, params, tokens, labels):
    emb = params["embed"]["embedding"] / c["fold_emb"]
    h = jnp.take(emb, tokens, axis=0) * c["emb_mult"]
    layer = jax.checkpoint(functools.partial(_layer, c, mode))
    h, _ = jax.lax.scan(lambda h, lw: (layer(h, lw), None), h,
                        params["layers"][0])
    x = _rms(h, params["final_norm"]["scale"] / c["fold_final"], c["eps"])
    logits = _ein("bsd,vd->bsv", x, emb[:c["vocab"]], mode) \
        / c["logits_div"]
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)


def _lr(o: dict, step: int) -> float:
    if step < o["warmup_steps"]:
        return o["lr"] * step / max(o["warmup_steps"], 1)
    t = min(max((step - o["warmup_steps"])
                / max(o["total_steps"] - o["warmup_steps"], 1), 0.0), 1.0)
    f = o["min_lr_frac"] + (1 - o["min_lr_frac"]) * 0.5 \
        * (1 + math.cos(math.pi * t))
    return o["lr"] * f


@jax.jit
def _adam(p, g, mu, nu, b1, b2, b1c, b2c, eps, wd, lr, scale):
    g = g * scale
    mu = b1 * mu + (1 - b1) * g
    nu = b2 * nu + (1 - b2) * g * g
    p = p - lr * ((mu / b1c) / (jnp.sqrt(nu / b2c) + eps) + wd * p)
    return p, mu, nu


@functools.partial(jax.jit, static_argnames=("vocab", "stacked", "emb"))
def _norms(x, *, vocab, stacked, emb):
    if emb:
        x = x[:vocab]
    axes = tuple(range(1 if stacked else 0, x.ndim))
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)), axis=axes))


def _slice_norms(tree, vocab: int, minus=None) -> dict:
    """Norm of each layer's leaf (``layers.<i>.<...>``; the embedding
    over its real rows), of ``tree - minus`` where given."""
    out = {}
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    other = jax.tree.leaves(minus) if minus is not None else [None] * len(flat)
    for (path, x), y in zip(flat, other):
        names = [str(getattr(k, "key", getattr(k, "idx", ""))) for k in path]
        stacked = names[0] == "layers"
        a = x if y is None else x - y
        v = np.asarray(_norms(a, vocab=vocab, stacked=stacked,
                              emb="embedding" in names))
        if stacked:
            for i, n in enumerate(v):
                out[".".join(["layers", str(i)] + names[2:])] = float(n)
        else:
            out[".".join(names)] = float(v)
    return out


def _placement(tree, mesh, n: int):
    """Each leaf split along its last axis that ``n`` divides."""
    def one(x):
        spec = [None] * x.ndim
        for ax in reversed(range(x.ndim)):
            if x.shape[ax] % n == 0:
                spec[ax] = "x"
                break
        return NamedSharding(mesh, P(*spec))
    return jax.tree.map(one, tree)


def steps(config: dict, p0, batches, mode: str = "f32") -> dict:
    """Follow AdamW from parameters ``p0`` (host arrays in the program's
    layout) over ``batches`` ((B, S + 1) token arrays).  Returns each
    step's loss, each layer leaf's norm of the first clipped gradient,
    and of the parameters' change over all the steps."""
    c = _C(_consts(config))
    o = config["train"]["opt"]
    devs = jax.devices()
    cpu = jax.devices("cpu")[0]
    mesh = Mesh(np.array(devs), ("x",))
    n = len(devs)
    host = jax.tree.map(lambda x: jax.device_put(np.asarray(x, np.float32),
                                                 cpu), p0)
    start = host
    mu = jax.tree.map(jnp.zeros_like, host)
    nu = jax.tree.map(jnp.zeros_like, host)
    place = _placement(host, mesh, n)
    grad_fn = jax.jit(jax.value_and_grad(functools.partial(_loss, c, mode)))
    losses, first = [], None
    for k, rows in enumerate(batches, start=1):
        b = rows.shape[0]
        bs = NamedSharding(mesh, P("x" if b % n == 0 else None, None))
        params = jax.device_put(host, place)
        loss, g = grad_fn(params, jax.device_put(rows[:, :-1], bs),
                          jax.device_put(rows[:, 1:], bs))
        losses.append(float(loss))
        jax.tree.map(lambda x: x.delete(), params)
        gnorm = math.sqrt(sum(float(jnp.sum(jnp.square(x)))
                              for x in jax.tree.leaves(g)))
        scale = min(1.0, o["clip_norm"] / (gnorm + 1e-9))
        g = jax.device_put(g, cpu)
        if k == 1:
            first = {name: v * scale for name, v in
                     _slice_norms(g, c["vocab"]).items()}
        upd = functools.partial(
            _adam, b1=o["b1"], b2=o["b2"], b1c=1 - o["b1"] ** k,
            b2c=1 - o["b2"] ** k, eps=o["eps"], wd=o["weight_decay"],
            lr=_lr(o, k), scale=scale)
        new = jax.tree.map(upd, host, g, mu, nu)
        is_t = lambda x: isinstance(x, tuple)  # noqa: E731
        host = jax.tree.map(lambda t: t[0], new, is_leaf=is_t)
        mu = jax.tree.map(lambda t: t[1], new, is_leaf=is_t)
        nu = jax.tree.map(lambda t: t[2], new, is_leaf=is_t)
    return {"losses": losses, "grad": first,
            "change": _slice_norms(host, c["vocab"], start)}
