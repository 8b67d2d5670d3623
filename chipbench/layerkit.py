"""What the per-layer readers share: the loading of a reader, the
harness's steps matched to the traced ones, and the names by which the
trace shows the engine's programs and the paged-attention kernel."""

from __future__ import annotations

import importlib.util
import os
import re

from serve import percentile  # noqa: F401  (the readers' one percentile)

LAYERS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "layers")

DECODE = re.compile(r"jit__?decode")        # the decode program
CHUNK = re.compile(r"jit_chunk")            # a prefill-chunk program
JOIN = re.compile(r"jit_join")              # a whole-prompt prefill
PAGED_ATTN = re.compile(r"flash_decode")    # the paged-attention kernel


def load(path: str):
    """The reader module in file ``path`` (``layers/<metric>.py``)."""
    name = os.path.basename(path)[:-len(".py")]
    spec = importlib.util.spec_from_file_location(
        "layer_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str):
    """The ``read`` of metric ``name``, for a metric of another cell
    that is read in the same way."""
    return load(os.path.join(LAYERS, name + ".py")).read


def traced_steps(ctx) -> list:
    """(harness step, traced step) for every step that ran whole inside
    the trace, matched in order."""
    tr = ctx.get("trace")
    span = ctx.get("trace_span")
    if not tr or not span or not tr["steps"]:
        return []
    mine = [s for s in ctx["steps"] if s.t0 >= span[0] and s.t1 <= span[1]]
    theirs = tr["steps"]
    if len(mine) != len(theirs):
        raise ValueError(f"{len(mine)} steps in the traced window, "
                         f"{len(theirs)} in the trace")
    return list(zip(mine, theirs))


def module_time(step: dict, pattern) -> tuple:
    """(seconds, runs) of the programs in a traced step that match."""
    t = n = 0
    for name, durs in step["modules"].items():
        if pattern.search(name):
            t += sum(durs)
            n += len(durs)
    return t, n


def op_time(step: dict, pattern, module=None) -> tuple:
    """(seconds, runs) of the operations in a traced step that match
    ``pattern``, in programs that match ``module`` (default: any)."""
    t = n = 0
    for (mod, name), (d, k) in step["ops"].items():
        if pattern.search(name) and (module is None or module.search(mod)):
            t += d
            n += k
    return t, n
