"""Arena-backed scratch for codec band payloads (ROADMAP: arena codecs).

The SPNG codec decodes band by band: each band needs a decompressed
payload buffer, dead as soon as the bands are concatenated into the
caller's result.  Per-band scratch is a bump-pointer slice from a
thread-local :class:`repro.runtime.memory.FrameArena`, so steady-state
decode touches the allocator zero times (each producer worker thread owns
its own arena, so there is no cross-worker lock traffic either).  SJPG
needs none of it: it entropy-decodes every band of an item into one
coefficient buffer allocated per call (``jpeg.decode_to_coefficients``).

Usage (inside a codec):

    with band_scratch() as scratch:
        buf = scratch.alloc_bytes(n)          # uint8 view
        ...  # slices all release when the block exits

The arena import is deferred so ``repro.preprocessing`` stays importable
without ``repro.runtime`` (the runtime package imports preprocessing at
init time).
"""

from __future__ import annotations

import threading
from contextlib import contextmanager

import numpy as np

_TLS = threading.local()


def _arena():
    arena = getattr(_TLS, "arena", None)
    if arena is None:
        from repro.runtime.memory import FrameArena

        arena = _TLS.arena = FrameArena(block_bytes=1 << 20)
    return arena


def arena_stats():
    """This thread's codec-scratch arena occupancy (ArenaStats)."""
    return _arena().stats()


class BandScratch:
    """Scoped allocator over the thread-local arena; releases on exit."""

    def __init__(self):
        self._slices = []

    def alloc_bytes(self, nbytes: int) -> np.ndarray:
        """Uninitialized uint8 scratch of ``nbytes`` (an arena slice view).

        Requests round up to 64-byte multiples so successive slices stay
        aligned for typed views (arena blocks bump-allocate)."""
        nbytes = int(nbytes)
        sl = _arena().alloc(-(-nbytes // 64) * 64)
        self._slices.append(sl)
        return sl.array[:nbytes]

    def release(self) -> None:
        slices, self._slices = self._slices, []
        for sl in reversed(slices):
            sl.release()


@contextmanager
def band_scratch():
    scratch = BandScratch()
    try:
        yield scratch
    finally:
        scratch.release()
