"""§Roofline: three-term roofline per (arch x shape) from dry-run artifacts.

    compute term    = HLO_FLOPs_per_device / peak_FLOP/s
    memory term     = HBM_bytes_per_device / HBM_bw
    collective term = collective_bytes_per_device / link_bw

FLOPs and collective bytes come from the compiled (partitioned) HLO of the
analysis lowering (launch/dryrun.py).  HBM bytes use an analytic traffic
model (documented below): the CPU backend's ``bytes accessed`` counts
every unfused elementwise op — TPU fusion eliminates most of that traffic,
so raw HLO bytes are reported only as an upper bound (``hlo_bytes``).

Hardware (TPU v5e): 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI.
"""

from __future__ import annotations

import json
import os

from benchmarks.common import emit
from repro.configs import ARCHS, SHAPES, cells, get_config
from repro.core import target_for
from repro.models.config import ModelConfig

# the dry-run cells model a v5e pod: peaks from the one device-kind table
# the kernel profiler reads too (repro.core.DEVICE_TARGETS)
DEVICE_KIND = "TPU v5 lite"
_TARGET = target_for(DEVICE_KIND)
PEAK_FLOPS = _TARGET.peak_bf16_flops
HBM_BW = _TARGET.hbm_bytes_per_s
LINK_BW = _TARGET.ici_bytes_per_s_per_link
ART_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments",
                       "dryrun")


def analytic_hbm_bytes(cfg: ModelConfig, shape_name: str,
                       data_ax: int = 16, model_ax: int = 16) -> float:
    """Per-device HBM traffic per step (documented in EXPERIMENTS.md).

    train:   weights read twice (fwd+bwd) at the TP shard size, gradient +
             AdamW state at the FSDP shard size, layer activations saved
             once and re-read + one recompute pass (block remat), logits
             3 passes.
    prefill: weights once, activations twice, KV-cache written.
    decode:  active weights once + KV cache read once (the classic
             decode memory wall).
    """
    shape = SHAPES[shape_name]
    n_dev = data_ax * model_ax
    p_total = cfg.param_count()
    p_active = cfg.active_param_count()
    bpe = 2
    tokens_loc = shape.seq_len * shape.global_batch / data_ax
    if shape.kind == "decode":
        tokens_loc = shape.global_batch / max(
            data_ax if shape.global_batch >= data_ax else 1, 1)

    d = cfg.d_model
    layers = cfg.n_layers + cfg.encoder_layers
    act_pass = layers * tokens_loc * d * bpe

    # decode-cache size per device
    cache_bytes = 0.0
    for i in range(cfg.n_layers):
        m = cfg.mixer_for_layer(i)
        if m == "global":
            cache_bytes += (shape.global_batch * shape.seq_len *
                            cfg.n_kv_heads * cfg.head_dim * 2 * bpe)
        elif m == "local":
            cache_bytes += (shape.global_batch *
                            min(cfg.window or shape.seq_len, shape.seq_len)
                            * cfg.n_kv_heads * cfg.head_dim * 2 * bpe)
        elif m == "ssd":
            cache_bytes += shape.global_batch * (
                cfg.ssm_heads * cfg.ssm_head_dim * cfg.ssm_state * 4 +
                (cfg.conv_width - 1) * (cfg.d_inner + 2 * cfg.ssm_state)
                * bpe)
        elif m == "recurrent":
            cache_bytes += shape.global_batch * cfg.lru_width * (4 + 3 * bpe)
    cache_loc = cache_bytes / n_dev

    vocab_loc = cfg.vocab / model_ax

    if shape.kind == "train":
        w = 2 * (p_active / model_ax) * bpe          # fwd + bwd reads
        opt = (p_total / n_dev) * (2 * bpe + 16 + 6)  # grads + moments
        act = 4 * act_pass                            # save/read/recompute
        logits = 3 * tokens_loc * vocab_loc * bpe
        return w + opt + act + logits
    if shape.kind == "prefill":
        w = (p_active / model_ax) * bpe
        act = 2 * act_pass
        return w + act + cache_loc
    # decode: one token
    w = (p_active / model_ax) * bpe
    return w + cache_loc


def load_cells(mesh: str = "16x16") -> list[dict]:
    out = []
    for arch, shape in cells():
        safe = arch.replace("/", "_").replace(".", "_")
        path = os.path.join(ART_DIR, f"{safe}__{shape}__{mesh}.json")
        if os.path.exists(path):
            with open(path) as f:
                out.append(json.load(f))
    return out


def terms(rec: dict) -> dict | None:
    if not rec.get("ok") or rec.get("flops") is None:
        return None
    cfg = get_config(rec["arch"])
    t_c = rec["flops"] / PEAK_FLOPS
    hbm = analytic_hbm_bytes(cfg, rec["shape"])
    t_m = hbm / HBM_BW
    t_x = rec["collective_bytes_total"] / LINK_BW
    dominant = max(("compute", t_c), ("memory", t_m),
                   ("collective", t_x), key=lambda kv: kv[1])
    shape = SHAPES[rec["shape"]]
    if shape.kind == "train":
        toks = shape.seq_len * shape.global_batch
    elif shape.kind == "prefill":
        toks = shape.seq_len * shape.global_batch
    else:
        toks = shape.global_batch
    model_flops = cfg.model_flops_per_token() * toks / 256  # per device
    if shape.kind != "train":
        model_flops /= 3  # fwd only (6ND counts fwd+bwd)
    return {
        "compute_s": t_c, "memory_s": t_m, "collective_s": t_x,
        "bottleneck": dominant[0],
        "roofline_fraction": t_c / max(t_c, t_m, t_x),
        "model_hlo_ratio": model_flops / rec["flops"],
        "hlo_bytes_upper": rec.get("bytes_accessed"),
    }


def run() -> None:
    recs = load_cells()
    for rec in recs:
        t = terms(rec)
        if t is None:
            emit(f"roofline/{rec['arch']}/{rec['shape']}", 0.0,
                 "missing-analysis")
            continue
        emit(f"roofline/{rec['arch']}/{rec['shape']}", 0.0,
             f"compute={t['compute_s']*1e3:.1f}ms "
             f"mem={t['memory_s']*1e3:.1f}ms "
             f"coll={t['collective_s']*1e3:.1f}ms "
             f"bottleneck={t['bottleneck']} "
             f"frac={t['roofline_fraction']:.2f} "
             f"useful={t['model_hlo_ratio']:.2f}")


if __name__ == "__main__":
    run()
