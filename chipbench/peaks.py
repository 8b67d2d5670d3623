"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Copied from the program's ``core.DEVICE_TARGETS`` so that no later
change to the program moves the yardstick.  Source: Google Cloud
documentation, "TPU v5e": 197 TFLOP/s in bf16, 393 TOP/s in int8,
16 GB of HBM at 819 GB/s.  A kind that is not in the table is an
error: a roofline priced against another chip's peaks is wrong.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float        # FLOP/s
    int8_ops: float          # OP/s
    hbm_bytes_per_s: float
    hbm_bytes: float


PEAKS = {
    "TPU v5 lite": Peaks(bf16_flops=197e12, int8_ops=393e12,
                         hbm_bytes_per_s=819e9, hbm_bytes=16e9),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None
