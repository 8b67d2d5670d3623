"""Aliased paged K/V writer: new tokens into the page pools, in place.

Decode and prefill-chunk programs write each step's K/V rows into the
page pools (``serve/kv_cache.py``) and read them back through
``flash_decode``.  An XLA scatter into a pool pins it to a page-minor
layout that the head-major kernel cannot read, so every layer of a hot
program would pay a slice, a relayout and a write-back of its whole pool
(docs/serving.md, "The pool contract").  This kernel takes the pools in
``pl.ANY`` with ``input_output_aliases``: the pools never leave HBM and
only the sublane tiles that hold new rows move.

Layouts:

* ``k/v_pages``: (n_pages, Hkv, page, D), or the stacked
  (n_layers, n_pages, Hkv, page, D) with ``layer`` picking the layer;
* ``k``/``v``:   (B, S, Hkv, D) — S consecutive new positions per slot,
  the first at ``pos[b]``;
* ``block_tables``: (B, n_blocks) — physical page per logical block.

A position's row sits in the aligned tile of ``rows`` sublanes (the
dtype's native tile: 8 rows of 4 bytes, 16 of 2, 32 of 1) that holds it.
A tile the span covers whole is written straight from the new rows; a
tile it covers in part is read, merged under a row mask and written back.
Every read and whole-tile write of a call is issued before the first
wait, so one call costs a few DMA latencies, not one per slot.  Positions at or past ``max_seq``
(the padded tail of a last prefill chunk, draft rows past the budget)
are dropped; a slot whose block-table row is masked to the scratch page
writes there, as decode's inactive slots do.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_decode import layer_stack

# the tile table, per tile: physical page, first row in the page, first
# and last+1 row of the tile that the span writes, the merge buffer of a
# partial tile, and what to do with the tile
_PAGE, _ROW0, _LO, _HI, _BUF, _KIND = range(6)
_META = 6
_SKIP, _FULL, _PARTIAL = 0, 1, 2


def _tile_rows(dtype, page: int) -> int:
    """Sublane rows of one aligned tile of a ``dtype`` pool: the native
    (8 x 32-bit) tile, or the whole page when the page does not hold a
    whole number of them."""
    rows = 8 * 4 // jnp.dtype(dtype).itemsize
    return rows if page % rows == 0 else page


def _kv_write_kernel(meta_ref, layer_ref, knew_ref, vnew_ref, _k_in, _v_in,
                     k_ref, v_ref, kold_ref, vold_ref, rsem, wsem, *,
                     rows: int):
    # the pools come in aliased to k_ref / v_ref: every DMA goes there.
    # Index arithmetic lives in the tile table, so tracing and lowering
    # stay cheap: the engine builds one such kernel per program shape.
    layer = layer_ref[0]
    pools, news, olds = (k_ref, v_ref), (knew_ref, vnew_ref), (kold_ref,
                                                                 vold_ref)

    def field(t, f):                # t: the tile's first table entry
        return meta_ref[t + f] if f else meta_ref[t]

    def tile(w, t):
        row0 = pl.multiple_of(field(t, _ROW0), rows)
        return pools[w].at[layer, field(t, _PAGE), :, pl.ds(row0, rows), :]

    def read(w, t):     # own semaphore: the merge waits for its own tile
        buf = field(t, _BUF)
        return pltpu.make_async_copy(tile(w, t), olds[w].at[buf],
                                     rsem.at[w, buf])

    def write(w, t, src):
        return pltpu.make_async_copy(src, tile(w, t), wsem.at[w])

    def each_tile(fn):
        def body(i, carry):
            t = jax.lax.mul(i, jnp.int32(_META))
            fn(i, t, field(t, _KIND))
            return carry
        jax.lax.fori_loop(0, knew_ref.shape[0], body, 0)

    def when(kind, want):
        return pl.when(jax.lax.eq(kind, jnp.int32(want)))

    def issue(i, t, kind):          # every read and whole-tile write
        @when(kind, _PARTIAL)
        def _():
            for w in (0, 1):
                read(w, t).start()

        @when(kind, _FULL)
        def _():
            for w in (0, 1):
                write(w, t, news[w].at[i]).start()

    def merge(i, t, kind):             # partial tiles: merge, write back
        @when(kind, _PARTIAL)
        def _():
            r = jax.lax.broadcasted_iota(jnp.int32, knew_ref.shape[1:], 1)
            keep = (r >= field(t, _LO)) & (r < field(t, _HI))
            buf = field(t, _BUF)
            for w in (0, 1):
                read(w, t).wait()
                olds[w][buf] = jnp.where(keep, news[w][i], olds[w][buf])
                write(w, t, olds[w].at[buf]).start()

    def drain(i, t, kind):          # every write is one tile
        @pl.when(jax.lax.ne(kind, jnp.int32(_SKIP)))
        def _():
            for w in (0, 1):
                write(w, t, news[w].at[i]).wait()

    each_tile(issue)
    each_tile(merge)
    each_tile(drain)


def _tile_meta(block_tables, pos, s: int, page: int, rows: int,
               max_seq: int, n_tiles: int, per_slot: int):
    """(B * n_tiles * _META,) int32: the tile table of every tile a
    slot's span can touch, slot-major."""
    b, nb = block_tables.shape
    pos = pos.astype(jnp.int32)
    start = pos - pos % rows                              # (B,)
    j = jnp.arange(n_tiles, dtype=jnp.int32)
    tp = start[:, None] + rows * j
    end = jnp.minimum(pos + s, max_seq)                   # (B,)
    lo = jnp.clip(pos[:, None] - tp, 0, rows)
    hi = jnp.maximum(jnp.clip(end[:, None] - tp, 0, rows), lo)
    blk = jnp.minimum(tp // page, nb - 1)
    page_idx = jnp.take_along_axis(block_tables.astype(jnp.int32), blk,
                                   axis=1)
    # a span touches its partial tiles only at its two ends
    buf = (jnp.arange(b, dtype=jnp.int32)[:, None] * per_slot
           + jnp.minimum(j, per_slot - 1)[None, :])
    kind = jnp.where(lo == hi, _SKIP,
                     jnp.where((lo == 0) & (hi == rows), _FULL, _PARTIAL))
    meta = jnp.stack([page_idx, tp % page, lo, hi, buf, kind], -1)
    return meta.astype(jnp.int32).reshape(-1)


def _aligned_rows(x, pos, rows: int, n_tiles: int):
    """(B, S, Hkv, D) -> (B * n_tiles, Hkv, rows, D): each slot's span
    shifted to its first tile's row offset, laid out as the pool's tiles.
    The shift is chosen among the ``rows`` static ones by a select, not a
    per-slot update, which XLA would run as a loop over the slots."""
    b, s, hkv, d = x.shape
    width = n_tiles * rows
    off = jax.lax.broadcast_in_dim((pos % rows).astype(jnp.int32),
                                   (b, width, hkv, d), (0,))
    zero = jnp.zeros((), x.dtype)
    placed = jnp.zeros((b, width, hkv, d), x.dtype)
    for o in range(rows):
        shifted = jax.lax.pad(x, zero, [(0, 0, 0), (o, width - s - o, 0),
                                        (0, 0, 0), (0, 0, 0)])
        placed = jax.lax.select(jax.lax.eq(off, jnp.full_like(off, o)),
                                shifted, placed)
    return (placed.reshape(b, n_tiles, rows, hkv, d)
                  .transpose(0, 1, 3, 2, 4)
                  .reshape(b * n_tiles, hkv, rows, d))


@functools.partial(jax.jit, static_argnames=("max_seq", "interpret"))
def write_kv(k_pages: jax.Array, v_pages: jax.Array, k: jax.Array,
             v: jax.Array, block_tables: jax.Array, pos: jax.Array,
             layer: jax.Array | int | None = None, *,
             max_seq: int | None = None,
             interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """Write ``k``/``v`` (B, S, Hkv, D) at positions ``pos[b] + t`` of
    each slot into the pools, in place; returns the updated pools (the
    same buffers under jit with the pools donated or carried)."""
    stacked = k_pages.ndim == 5
    k_pages, v_pages, lay = layer_stack(k_pages, v_pages, layer)
    b, s, hkv, d = k.shape
    page = k_pages.shape[-2]
    max_seq = block_tables.shape[1] * page if max_seq is None else max_seq
    rows = _tile_rows(k_pages.dtype, page)
    n_tiles = (rows + s - 2) // rows + 1
    per_slot = min(n_tiles, 2)
    meta = _tile_meta(block_tables, pos, s, page, rows, max_seq, n_tiles,
                      per_slot)
    knew = _aligned_rows(k.astype(k_pages.dtype), pos, rows, n_tiles)
    vnew = _aligned_rows(v.astype(v_pages.dtype), pos, rows, n_tiles)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    anym = pl.BlockSpec(memory_space=pl.ANY)
    old = pltpu.VMEM((b * per_slot, hkv, rows, d), k_pages.dtype)
    kp, vp = pl.pallas_call(
        functools.partial(_kv_write_kernel, rows=rows),
        in_specs=[smem, smem, vmem, vmem, anym, anym],
        out_specs=(anym, anym),
        out_shape=(jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
                   jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype)),
        scratch_shapes=[old, old,
                        pltpu.SemaphoreType.DMA((2, b * per_slot)),
                        pltpu.SemaphoreType.DMA((2,))],
        input_output_aliases={4: 0, 5: 1},
        interpret=interpret,
    )(meta, lay, knew, vnew, k_pages, v_pages)
    if not stacked:
        kp, vp = kp[0], vp[0]
    return kp, vp


def write_kv_ref(k_pages: jax.Array, v_pages: jax.Array, k: jax.Array,
                 v: jax.Array, block_tables: jax.Array, pos: jax.Array,
                 layer: jax.Array | int | None = None, *,
                 max_seq: int | None = None) -> tuple[jax.Array, jax.Array]:
    """jnp oracle of :func:`write_kv`: one XLA scatter per pool, dropping
    positions at or past ``max_seq`` as the kernel does.  Off the hot
    path only: inside a decode or chunk program it re-lays the pool
    (docs/serving.md, "The pool contract")."""
    b, s = k.shape[:2]
    n_pages, _, page, _ = k_pages.shape[-4:]
    nb = block_tables.shape[1]
    max_seq = nb * page if max_seq is None else max_seq
    positions = pos[:, None] + jnp.arange(s, dtype=pos.dtype)[None, :]
    blk = jnp.minimum(positions // page, nb - 1)
    page_idx = jnp.where(positions < max_seq,
                         block_tables[jnp.arange(b)[:, None], blk],
                         n_pages)                         # dropped
    at = (page_idx, slice(None), positions % page)
    if layer is not None:
        at = (layer,) + at
    return (k_pages.at[at].set(k.astype(k_pages.dtype), mode="drop"),
            v_pages.at[at].set(v.astype(v_pages.dtype), mode="drop"))
