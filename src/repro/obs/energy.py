"""Per-op energy pricing of a kernel dispatch (docs/observability.md).

The paper's energy model (``core.energy`` / ``core.hierarchy``) prices a
blocking string: every on-chip buffer at its size-dependent SRAM access
cost, the DRAM boundary at the fixed per-16-byte cost, plus the MAC
array.  :func:`op_energy_pj` gives that split for the schedule a kernel
runs, with one correction: the DRAM component is priced on the kernel's
exact HBM bytes (the grid's block transfers, ``kernels.*.hbm_bytes``)
rather than the model's idealized stream, so a schedule moving more
bytes than the analytic winner shows up in picojoules too.

Everything returns plain JSON-safe dicts; ops whose resolved tiles the
kernels cannot run directly (non-dividing — the oracle-fallback path)
price as ``None``, the same convention the DRAM ledger uses for bytes.
"""

from __future__ import annotations

from repro.core.energy import DRAM_PJ_PER_16B


def op_energy_pj(spec, tiles: tuple[int, ...],
                 dram_bytes: int | None) -> dict | None:
    """Energy split (pJ) of one kernel dispatch under ``tiles``.

    ``dram_bytes`` is the measured per-call HBM traffic attributed to
    the dispatch; the SRAM and MAC components come from the paper's
    model evaluated on the same blocking string the kernel executes
    (``tune.schedule_to_string``).  Returns ``None`` when the tiles do
    not divide the problem (the kernel took its fallback, so there is
    no blocking string to price).
    """
    from repro.tune import schedule_to_string
    from repro.tune.lowering import divides
    from repro.core.hierarchy import energy_custom

    if dram_bytes is None or not divides(spec, tiles):
        return None
    rep = energy_custom(schedule_to_string(spec, tiles))
    # measured-DRAM re-price at 320 pJ per 16-bit word (2 bytes)
    dram_pj = dram_bytes / 2.0 * DRAM_PJ_PER_16B
    sram_pj = max(rep.mem_pj - rep.dram_pj, 0.0)
    mac_pj = rep.mac_pj
    total = dram_pj + sram_pj + mac_pj
    macs = spec.problem().macs
    return {
        "dram_pj": dram_pj,
        "sram_pj": sram_pj,
        "mac_pj": mac_pj,
        "total_pj": total,
        "pj_per_mac": total / macs if macs else None,
    }
