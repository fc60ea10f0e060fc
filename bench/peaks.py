"""Published per-chip peaks, keyed by JAX ``device_kind``.

The benchmark's own copy, so that the yardstick does not move when the
program does.  Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s
in bf16, 393 TOP/s in int8, 16 GB of HBM at 819 GB/s per chip.  A device
kind that is not here is an error: there is no default chip.
"""

from __future__ import annotations

import dataclasses

__all__ = ["Peaks", "PEAKS", "peaks"]


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float    # bf16 FLOP/s per chip
    hbm_bw: float   # HBM bytes/s per chip

    def bound_s(self, flops: float, nbytes: float) -> float:
        """Least time the chip can take for ``flops`` and ``nbytes``."""
        return max(flops / self.flops, nbytes / self.hbm_bw)


PEAKS = {"TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9)}


def peaks(device_kind: str) -> Peaks:
    """Peaks of ``device_kind``; ``KeyError`` for a kind not in the table."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}"
                       f" (known: {sorted(PEAKS)})") from None
