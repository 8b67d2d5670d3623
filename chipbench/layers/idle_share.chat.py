"""Share of the traced window in which no operation ran on the device,
leaving out the time the harness waited for arrivals with no request in
the engine."""

import layerkit


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr["devices"] or not layerkit.traced_steps(ctx):
        return None
    busy_window = tr["window_s"] - tr["wait_s"]
    return 100.0 * (1.0 - tr["busy_s"] / busy_window)
