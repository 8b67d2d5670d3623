"""Device time per run of a prefill-chunk program, over the traced
steps."""

import layerkit


def read(ctx):
    t = n = 0
    for _, st in layerkit.traced_steps(ctx):
        dt, dn = layerkit.module_time(st, layerkit.CHUNK)
        t, n = t + dt, n + dn
    return t / n * 1e3 if n else None
