"""Device idle time in an engine step: the mean time inside an
``engine.step`` span in which the device ran no operation, that is the
time the chip waited on the engine's host code."""

import spans


def read(ctx):
    steps = spans.run_steps(ctx)
    if not steps:
        return None
    return sum(s["idle_s"] for s in steps) / len(steps) * 1e3
