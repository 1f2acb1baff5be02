"""Operations and bytes of the grouped, templated ed25519 verify kernel,
from its shapes, and its least time on a device of `peaks.json`.

Counted from `ops/ed25519.verify_grouped` as it is written (PR 21):
per lane 26 mixed additions from the validator's 10-bit comb table and
22 from the 12-bit base table (7 field multiplications each), one
complete addition (9) and its share of the batched encode (~5); a field
multiplication is a 32 x 32 limb product, 1,024 multiply-adds.  SHA-512
of the 192-byte challenge and the scalar reductions are integer work
that the matrix unit does not see: counted as 0, which makes the share a
little low, never high.  Bytes: each mixed addition gathers one 3 x 32
byte table entry; a lane brings 72 bytes (signature, two indices) and
returns 1; the templates come once.
"""

from __future__ import annotations

import json
import os

COMB_ADDS, BASE_ADDS = 26, 22
MULS_PER_MIXED_ADD, MULS_FULL_ADD, MULS_ENCODE = 7, 9, 5
FLOPS_PER_FIELD_MUL = 2 * 32 * 32
ENTRY_BYTES = 3 * 32
LANE_BYTES = 64 + 4 + 4 + 1
TEMPLATE_BYTES = 128


def verify_ops_bytes(lanes: int, templates: int) -> tuple[float, float]:
    muls = ((COMB_ADDS + BASE_ADDS) * MULS_PER_MIXED_ADD + MULS_FULL_ADD +
            MULS_ENCODE)
    flops = float(lanes) * muls * FLOPS_PER_FIELD_MUL
    nbytes = (float(lanes) * ((COMB_ADDS + BASE_ADDS) * ENTRY_BYTES +
                              LANE_BYTES) + templates * TEMPLATE_BYTES)
    return flops, nbytes


def load_peaks(path: str | None = None) -> dict:
    path = path or os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "peaks.json")
    with open(path) as f:
        return json.load(f)["devices"]


def least_time_s(device_kind: str, flops: float, nbytes: float,
                 peaks: dict | None = None) -> tuple[float, str]:
    """(seconds, which bound).  An unknown device is an error."""
    peaks = peaks if peaks is not None else load_peaks()
    if device_kind not in peaks:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json")
    p = peaks[device_kind]
    t_c, t_m = flops / p["flops_per_s"], nbytes / p["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def roofline_pct(device_kind: str, lanes: int, templates: int,
                 kernel_s: float, peaks: dict | None = None):
    """(share in %, bound) of one call of the kernel that took
    `kernel_s` seconds of device time."""
    flops, nbytes = verify_ops_bytes(lanes, templates)
    t, bound = least_time_s(device_kind, flops, nbytes, peaks)
    return 100.0 * t / kernel_s, bound
