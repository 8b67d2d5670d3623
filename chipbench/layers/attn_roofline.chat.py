"""The paged-attention kernel's share of its roofline: the least time
the chip needs for the attention work of the traced decode steps and
prefill chunks (per call, the larger of its K/V, q and o bytes over
peak bandwidth and its FLOPs over peak FLOP/s) over the kernel's device
time in those programs.  Whole-prompt joins attend through another
path and are left out."""

import layerkit
import peaks
import work


def read(ctx):
    pk = None
    s = ctx["shape"]
    least = t = 0.0
    for mine, st in layerkit.traced_steps(ctx):
        dt, _ = layerkit.op_time(st, layerkit.PAGED_ATTN)
        if not dt:
            continue
        pk = pk or peaks.peaks_for(ctx["device_kind"])
        t += dt
        iters = max((n for _, n in mine.decode), default=0)
        for i in range(iters):
            ctxs = [b + i for b, n in mine.decode if n > i]
            least += max(work.attn_bytes(s, ctxs, len(ctxs))
                         / pk.hbm_bytes_per_s,
                         work.attn_flops(s, ctxs) / pk.bf16_flops)
        for start, n, prompt in mine.prefill:
            if start == 0 and n == prompt and n <= ctx["prefill_chunk"]:
                continue                        # a join
            w = work.prefill_chunk(s, start, n)
            least += max(w["attn_bytes"] / pk.hbm_bytes_per_s,
                         w["attn_flops"] / pk.bf16_flops)
    return 100.0 * least / t if t else None
