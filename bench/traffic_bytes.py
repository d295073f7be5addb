"""Bytes model of one fused zone-scan launch, and the chip peaks it is
divided by.

A copy of ``repro.core.planner.fused_sweep_slots`` and
``fused_traffic_bytes``, kept with the benchmark so that a change to the
program cannot move the yardstick of a roofline share.
"""

from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def n_limbs(l_max: int) -> int:
    """int32 limbs of one code: 4-bit digits, 7 to a limb, 2 per edge."""
    return -(-2 * int(l_max) // 7)


def sweep_slots(lo, hi, blk: int) -> int:
    """Slot cells a fused sweep streams: each block of ``blk`` candidate
    lanes reads its ``[lo, hi)`` window once."""
    return int(blk) * int(sum(int(h) - int(l) for l, h in zip(lo, hi)))


def traffic_bytes(*, n_slots: int, sweep_slots: int, blk: int,
                  l_max: int) -> int:
    """HBM bytes of one fused launch, int32 throughout: 5 arrays of chunk
    loads per swept slot over ``blk``, 3 lane loads per slot, and each
    lane's code limbs plus length written and read back once."""
    chunk = (int(sweep_slots) // int(blk)) * 5 * 4
    lanes = int(n_slots) * 3 * 4
    out = int(n_slots) * (n_limbs(l_max) + 1) * 4 * 2
    return chunk + lanes + out


def peaks(device_kind: str, path: str = PEAKS) -> dict:
    """Published peaks of ``device_kind``; a device not in the table is an
    error."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}")
    return table[device_kind]
