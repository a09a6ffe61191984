"""Bytes that one history-series reduction needs, from its shapes alone,
whatever implements it.  The tier holds float64 running energies, so
its entries count at 8 bytes; the per-device operands are read once per
flavour.
"""
from __future__ import annotations

# per device and flavour: covered boundary range (two int32), newest
# time, first time, hold cap, newest reading's density, running energy
# and sigma tolerance (six float64), reported and quarantine flags
_DEVICE_BYTES = 2 * 4 + 6 * 8 + 2


def series_bytes(instants: int, devices: int, flavours: int) -> int:
    """The tier's rows of ``instants`` boundaries for ``devices`` devices
    in ``flavours`` flavours (raw, corrected), plus each flavour's
    per-device operands: the least a series kernel reads."""
    return flavours * devices * (8 * instants + _DEVICE_BYTES)
