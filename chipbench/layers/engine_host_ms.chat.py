"""Host time of an engine step: the mean length of an ``engine.step``
span less its ``engine.readback`` spans, in which the host waits on the
device.  The engine layer's busy time."""

import spans


def read(ctx):
    steps = spans.run_steps(ctx)
    if not steps:
        return None
    return sum(s["t1"] - s["t0"] - s["readback_s"]
               for s in steps) / len(steps) * 1e3
