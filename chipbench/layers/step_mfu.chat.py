"""Model FLOPs of the traced steps' decoded and prefilled tokens over
the device time of the decode and prefill programs times the chip's
peak.  Masked rows of the batch and padded positions are not model
FLOPs."""

import layerkit
import peaks
import work


def read(ctx):
    flops = t = 0.0
    s = ctx["shape"]
    for mine, st in layerkit.traced_steps(ctx):
        for base, n in mine.decode:
            flops += work.matmul_flops(s, n) + work.attn_flops(
                s, [base + i for i in range(n)])
        for start, n, _ in mine.prefill:
            flops += work.prefill_chunk(s, start, n)["flops"]
        for pat in (layerkit.DECODE, layerkit.CHUNK, layerkit.JOIN):
            t += layerkit.module_time(st, pat)[0]
    if not t:
        return None
    return 100.0 * flops / (t * peaks.peaks_for(ctx["device_kind"])
                            .bf16_flops)
