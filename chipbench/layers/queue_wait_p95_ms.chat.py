"""95th percentile of the wait from a request's due time to its
admission to a slot, over every request due in the window (the harness
sees an admission at the start of the step that made it)."""

import layerkit


def read(ctx):
    window = [r for r in ctx["recs"].values()
              if 0 <= r.due < ctx["window_s"]]
    return layerkit.percentile(
        [((r.admit if r.admit is not None else ctx["t_end"]) - r.due) * 1e3
         for r in window], 95)
