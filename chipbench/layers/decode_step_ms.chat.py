"""Device time per decode step: the decode program's device time over
the steps it ran.  A decode program runs one step per layer's call of
the paged-attention kernel, so steps are those calls over the layers."""

import layerkit


def read(ctx):
    t = steps = 0
    for _, st in layerkit.traced_steps(ctx):
        dt, n = layerkit.module_time(st, layerkit.DECODE)
        if not n:
            continue
        _, calls = layerkit.op_time(st, layerkit.PAGED_ATTN,
                                    layerkit.DECODE)
        t += dt
        steps += calls / ctx["shape"].n_layers
    return t / steps * 1e3 if steps else None
