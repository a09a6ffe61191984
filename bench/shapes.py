"""Bytes that a layer's work needs, from its shapes alone, whatever
implements it.  Each value is counted once at 4 bytes, the width of the
monitor's kernel arithmetic (times enter relative to a per-slab anchor,
so 32 bits hold them); a faster path has to move at least this much.
"""
from __future__ import annotations

WORD = 4
# per device of a slab: state read (newest time and reading, has,
# run start, change count, gain, offset, time shift, window ends,
# hold cap, envelope ends) and written (newest reading, run start,
# change count, four energy increments, four reading moments, n_out)
_STATE_IN = 14
_STATE_OUT = 13
# per sample: the reading in; its two running energies, run duration
# and run flag out (the ring and the period histogram read them)
_SAMPLE_IN = 1
_SAMPLE_OUT = 4


def ingest_bytes(samples: int, devices: int, ticks: int = 0,
                 flat: bool = False) -> int:
    """One ingest slab: ``samples`` readings of ``devices`` devices.  A
    rectangular slab shares its ``ticks`` times; a flat one carries a
    time and a device id with every sample."""
    per_sample = _SAMPLE_IN + _SAMPLE_OUT + (2 if flat else 0)
    return WORD * (samples * per_sample + devices * (_STATE_IN + _STATE_OUT)
                   + ticks)
