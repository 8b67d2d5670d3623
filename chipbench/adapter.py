"""The one place where the benchmark reads the engine's internals.

The program has no public per-token progress hook yet, so the harness
reads each running request's progress (slot, prompt tokens cached,
tokens sampled) through ``engine.scheduler`` after every step, and
waits for the step's device work through the engine's output buffer.
When the engine gains a progress hook, only this file moves onto it.
"""

from __future__ import annotations

import jax


def progress(engine, rids) -> dict:
    """rid -> (slot, prompt tokens cached, tokens sampled) of every
    request in ``rids`` that holds a slot."""
    return {r.rid: (r.slot, r.prefilled, r.generated)
            for r in engine.scheduler.running.values() if r.rid in rids}


def waiting(engine) -> int:
    return len(engine.scheduler.waiting)


def sync(engine) -> None:
    """Wait until everything the last step dispatched has run, so that
    the tokens it sampled are visible to the host."""
    jax.block_until_ready(engine._out_buf)


def release(engine) -> None:
    """Free the engine's device state (page pools and slot buffers)."""
    for x in jax.tree.leaves((engine.cache, engine._out_buf, engine._hist,
                              engine._block_tables)):
        if isinstance(x, jax.Array) and not x.is_deleted():
            x.delete()


def page_size(cfg, sc) -> int:
    """The page size the engine will choose for ``sc``, which the pool's
    page count needs before the engine exists."""
    from repro.serve import kv_cache
    reuse = (sc.reuse_hint or None) if sc.prefix_cache else None
    return sc.page_size or kv_cache.choose_page_size(
        cfg, sc.max_seq, fused=sc.fuse, reuse_rate=reuse)


def set_params(engine, params) -> None:
    """Serve other weights of the same shapes (``None`` frees them)."""
    if params is None:
        for x in jax.tree.leaves(engine.params):
            x.delete()
    engine.params = params
