"""Mmap-backed raw-line storage for the pre stage.

The featurizers keep every kept raw line so the scorer can re-emit the
original row for flagged events (reference behavior: the post stage
re-reads the raw day, flow_post_lda.scala:245-248).  For a single day
that blob fits RAM, but a config-3 30-day corpus (BASELINE.json) does
not — and round 2 pickled the whole blob into features.pkl besides
(an early review's finding).  MmapBlob replaces the in-memory bytes with a
file-backed window: the OS pages rows in at emit time only, RSS stays
bounded by the numeric arrays, and pickling stores just the path.

Both native featurizers write the spill during ingest (the blob never
exists in RAM: native_src/flow_featurize.cpp ffz_set_spill,
native_src/dns_featurize.cpp dfz_set_spill); spill_bytes() remains for
post-hoc spilling of a container that was built in memory.
"""

from __future__ import annotations

import ctypes
import os

import numpy as np


class MmapBlob:
    """Read-only byte blob backed by a file via np.memmap.

    Supports the exact surface the feature containers use on their
    bytes blobs: len(), slicing (returns bytes), and a C pointer for
    the native emit path.  Pickles as the path — the spill file must
    travel with the day directory (features.pkl references it
    relatively to wherever the runner wrote it).
    """

    def __init__(self, path: str):
        self.path = path
        # Size at spill time, carried through the pickle: the runner's
        # post-move re-resolution uses it as an identity check, so a
        # stale same-named spill from an earlier interrupted run in a
        # copied day dir cannot be silently scored against mismatched
        # offsets (round-4 advisor finding).
        self.size: int | None = (
            os.path.getsize(path) if os.path.exists(path) else None
        )
        self._arr: np.ndarray | None = None

    def _a(self) -> np.ndarray:
        if self._arr is None:
            if os.path.getsize(self.path):
                self._arr = np.memmap(self.path, dtype=np.uint8, mode="r")
            else:
                self._arr = np.zeros(0, np.uint8)  # mmap rejects length 0
        return self._arr

    def __len__(self) -> int:
        return int(self._a().size)

    def __getitem__(self, key) -> bytes:
        return self._a()[key].tobytes()

    def as_c_char_p(self):
        """Pointer for ctypes calls (native emit).  numpy exposes the
        address of the read-only mapping directly — the C side only
        reads."""
        a = self._a()
        if a.size == 0:
            return b""
        return a.ctypes.data_as(ctypes.c_char_p)

    def __getstate__(self):
        return {"path": self.path, "size": self.size}

    def __setstate__(self, state):
        self.path = state["path"]
        self.size = state.get("size")  # pre-round-5 pickles lack it
        self._arr = None


def spill_bytes(blob: bytes, path: str) -> MmapBlob:
    """Write an in-memory blob to `path` and return its MmapBlob (the
    post-hoc spill used by the DNS container)."""
    with open(path, "wb") as f:
        f.write(blob)
    return MmapBlob(path)
