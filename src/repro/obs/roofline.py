"""Roofline attribution for per-contraction spans (paper §II-B).

The hardware ceilings live here — not in :mod:`repro.launch.roofline`,
which imports the model zoo and mutates ``XLA_FLAGS`` at import time and
therefore must never be reachable from the contraction hot path.  The
launcher-side roofline analysis imports its constants from this module,
so there is exactly one set of numbers.

Per contraction the attribution is the paper's arithmetic-intensity
analysis in record form:

* ``flops`` — ``2·∏ dims`` over every distinct mode
  (:func:`repro.core.planner.contraction_flops`);
* ``bytes`` — operand + output element counts × itemsize (the minimum
  traffic of a transpose-free evaluation — exactly what
  STRIDEDBATCHEDGEMM pays, and what a copy/transpose pipeline exceeds);
* ``intensity`` — flops / bytes;
* ``roofline_bound_us`` — ``max(flops/PEAK_FLOPS, bytes/HBM_BW)``: the
  time the roofline says this contraction cannot beat.

The ceilings are published per-chip peaks keyed by JAX ``device_kind``
(:data:`PEAKS`); analytic bounds are stated for the target chip
(:data:`TARGET_KIND`, a TPU v5e).  A *measured* share — a bound over a
time taken on a device — is only reported for a device with published
peaks: an accelerator of unknown kind raises (:func:`peaks`), and a CPU
reports none (:func:`device_peaks`), since a host timing is no device
time.

A span carrying ``roofline_bound_us`` gains ``roofline_fraction`` (bound
÷ measured duration) when it closes on such a device (see
:class:`repro.obs.trace.Tracer`) — ~1.0 means roofline-saturating, ≪1
means overhead or a wrong strategy.
Host-measured durations of *jit-traced* calls are trace time, not run
time; emitters flag those spans ``eager=False``.  The autotuner's cache
hits instead carry *measured* kernel time, giving the trustworthy
fraction (:func:`measured_fraction`).
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np

__all__ = [
    "PEAKS", "Peaks", "TARGET_KIND", "PEAK_FLOPS", "HBM_BW", "LINK_BW",
    "peaks", "device_peaks",
    "roofline_bound_us", "arithmetic_intensity",
    "contraction_record", "measured_fraction",
]


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float      # bf16 FLOP/s per chip
    hbm_bw: float     # HBM bytes/s per chip
    link_bw: float    # bytes/s per chip-to-chip link


#: Published per-chip peaks, keyed by JAX ``device_kind``.
#: TPU v5e ("TPU v5 lite"): Google Cloud documentation, "TPU v5e" —
#: 197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of
#: chip-to-chip interconnect over 4 links (50 GB/s each).
PEAKS = {"TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, link_bw=50e9)}

#: the chip analytic bounds are stated for
TARGET_KIND = "TPU v5 lite"
PEAK_FLOPS = PEAKS[TARGET_KIND].flops
HBM_BW = PEAKS[TARGET_KIND].hbm_bw
LINK_BW = PEAKS[TARGET_KIND].link_bw


def peaks(device_kind: str) -> Peaks:
    """Published peaks of ``device_kind``; an unknown kind raises
    ``KeyError`` — there is no default chip."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {sorted(PEAKS)})") from None


def device_peaks(device=None) -> Peaks | None:
    """Peaks of ``device`` (default: the first device), the chip a
    measured time was taken on.  ``None`` on a CPU, whose host timings
    are no device share; raises for an accelerator of unknown kind."""
    device = device or jax.devices()[0]
    if device.platform == "cpu":
        return None
    return peaks(device.device_kind)


def roofline_bound_us(flops: float, bytes_: float,
                      p: Peaks = PEAKS[TARGET_KIND]) -> float:
    """Minimum achievable µs under the compute and memory ceilings of
    ``p`` (default: the target chip)."""
    return max(flops / p.flops, bytes_ / p.hbm_bw) * 1e6


def arithmetic_intensity(flops: float, bytes_: float) -> float:
    """Flops per byte moved (0.0 for a zero-byte degenerate case)."""
    return flops / bytes_ if bytes_ else 0.0


def measured_fraction(flops: float, bytes_: float, measured_us: float,
                      p: Peaks) -> float:
    """Achieved fraction of roofline from a kernel time measured on a
    chip with peaks ``p`` (:func:`device_peaks`)."""
    if measured_us <= 0:
        return 0.0
    return roofline_bound_us(flops, bytes_, p) / measured_us


def contraction_record(cs, dims: dict, dtype) -> dict:
    """The attribution attributes of one pairwise contraction.

    ``cs`` is a :class:`repro.core.notation.ContractionSpec`, ``dims``
    the mode→size map, ``dtype`` the operand result type.  Pure
    arithmetic — safe in any layer, cheap enough to run per traced span.
    """
    from repro.core.planner import contraction_flops, modes_size

    itemsize = int(np.dtype(dtype).itemsize)
    flops = contraction_flops(cs, dims)
    nbytes = itemsize * (
        modes_size(cs.a_modes, dims)
        + modes_size(cs.b_modes, dims)
        + modes_size(cs.c_modes, dims)
    )
    return {
        "spec": cs.spec_str(),
        "dtype": np.dtype(dtype).name,
        "flops": int(flops),
        "bytes": int(nbytes),
        "intensity": arithmetic_intensity(flops, nbytes),
        "roofline_bound_us": roofline_bound_us(flops, nbytes),
    }
