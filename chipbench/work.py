"""Operations and bytes that the work needs, computed from shapes.

These are the yardstick's own counts: what a dense decoder layer stack
of the configuration must compute and move, whichever kernel does it.
A matrix multiplication of (m, k) by (k, n) is 2mkn FLOPs.  Bytes are
the least traffic to HBM: each weight once per call, each K/V element
read once per call, q and o once.  Sizes are the configuration's
(``Shape``, which a family's ``shape`` builds from its file); the
element width is that of the served dtype.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Shape:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    elem_bytes: int = 2          # bf16 weights, activations and K/V

    @property
    def layer_matmul_params(self) -> int:
        """Weights one layer multiplies by: q, k, v, o and the three
        SwiGLU projections."""
        d, hd = self.d_model, self.head_dim
        attn = d * hd * (self.n_heads + 2 * self.n_kv_heads) \
            + self.n_heads * hd * d
        return attn + 3 * d * self.d_ff

    @property
    def matmul_params(self) -> int:
        """Every weight a token multiplies by, LM head included."""
        return self.n_layers * self.layer_matmul_params \
            + self.vocab * self.d_model

    @property
    def weight_bytes(self) -> int:
        """Weights a step must read once: the layers' projections and
        the (tied) embedding as LM head."""
        return self.matmul_params * self.elem_bytes

    @property
    def kv_bytes_per_token(self) -> int:
        """K and V of one position, over every layer."""
        return (2 * self.n_kv_heads * self.head_dim * self.elem_bytes
                * self.n_layers)


def matmul_flops(s: Shape, tokens: int) -> float:
    """Projection and LM-head FLOPs of ``tokens`` positions."""
    return 2.0 * s.matmul_params * tokens


def attn_flops(s: Shape, contexts) -> float:
    """Score and value FLOPs of queries at the given context lengths
    (keys attended, the query's own included), over every layer."""
    return 4.0 * s.n_heads * s.head_dim * s.n_layers * float(sum(contexts))


def attn_bytes(s: Shape, read_contexts, queries: int) -> float:
    """Least HBM traffic of one paged-attention call per layer, summed
    over layers: the K/V of each row's context read once
    (``read_contexts``: one entry per batch row), q and o once per
    query position."""
    kv = sum(read_contexts) * s.kv_bytes_per_token
    qo = 2 * queries * s.n_heads * s.head_dim * s.elem_bytes * s.n_layers
    return float(kv + qo)


def decode_step(s: Shape, contexts) -> dict:
    """One decode step of a batch whose rows attend ``contexts`` keys:
    FLOPs, and bytes (weights once, each row's K/V once)."""
    n = len(contexts)
    attn_b = attn_bytes(s, contexts, n)
    return {"flops": matmul_flops(s, n) + attn_flops(s, contexts),
            "bytes": s.weight_bytes + attn_b,
            "attn_flops": attn_flops(s, contexts), "attn_bytes": attn_b}


def prefill_chunk(s: Shape, start: int, tokens: int) -> dict:
    """One prefill chunk of ``tokens`` positions after ``start`` cached
    ones: query i attends ``start + i + 1`` keys."""
    ctx = [start + i + 1 for i in range(tokens)]
    attn_b = attn_bytes(s, [start + tokens], tokens)
    return {"flops": matmul_flops(s, tokens) + attn_flops(s, ctx),
            "bytes": s.weight_bytes + attn_b,
            "attn_flops": attn_flops(s, ctx), "attn_bytes": attn_b}


def train_step(s: Shape, batch: int, seq: int) -> float:
    """Forward and backward FLOPs of a step on ``batch`` sequences of
    ``seq`` tokens (3x the forward; recomputation not counted)."""
    tokens = batch * seq
    ctx = [i + 1 for i in range(seq)]
    fwd = matmul_flops(s, tokens) + batch * attn_flops(s, ctx)
    return 3.0 * fwd
