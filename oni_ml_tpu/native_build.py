"""Shared build/load machinery for the native (C++) components.

Each native module is one translation unit under ``oni_ml_tpu/native_src/`` compiled to
its own .so beside the Python wrapper that binds it.  Loading strategy
(shared by io/native.py and features/native_flow.py): use the prebuilt
.so (``make -C native``); if missing or older than its source, compile
once on demand with g++; if neither works the caller falls back to pure
Python.  ``ONI_ML_TPU_NO_NATIVE=1`` forces the Python paths.

The .so files are git-ignored build products, so a clean checkout
builds them on first use.  ``NativeLib.status`` says which of the three
happened (``prebuilt`` | ``built`` | ``python-fallback``) and
``load_all()`` reports it for every library — chip_smoke.py prints the
result and refuses a day featurized by the fallback.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Callable

import numpy as np

# Every NativeLib constructed in this process, in construction order
# (each wrapper module owns exactly one, built at import).
_LIBRARIES: "list[NativeLib]" = []


# PyBytes_FromStringAndSize with a true Py_ssize_t size.  CPython's
# ctypes.string_at truncates its size argument to a C `int`, so any
# native buffer >= 2 GiB arrives as a negative size and raises
# SystemError — first hit by the realistic-cardinality 30-day
# word_counts emit (round 5: ~100M rows ≈ 3 GB in one blob).
# Private prototype (PYFUNCTYPE holds the GIL): assigning restype/
# argtypes on ctypes.pythonapi.<symbol> would mutate the process-global
# shared function object, racing any other library that prototypes the
# same symbol differently (round-5 review finding).
_PyBytes_FromStringAndSize = ctypes.PYFUNCTYPE(
    ctypes.py_object, ctypes.c_void_p, ctypes.c_ssize_t
)(("PyBytes_FromStringAndSize", ctypes.pythonapi))


def bytes_at(ptr, size: int) -> bytes:
    """64-bit-safe replacement for ctypes.string_at(ptr, size): copies
    `size` bytes from the native pointer into a bytes object.  Shared
    by native_emit.py and the feature containers."""
    if not size:
        return b""
    if not ptr:
        raise MemoryError("native buffer pointer is NULL")
    return _PyBytes_FromStringAndSize(ptr, size)


def narrow_counts_i32(counts: "np.ndarray") -> "np.ndarray":
    """int64 C-side counts -> int32 storage, guarded: astype wraps
    silently on overflow, which would corrupt corpus counts on an
    adversarial or multi-day aggregated input (round-3 advisor
    finding).  A single day can't reach 2^31 events per (ip, word)
    pair, but the invariant is now checked, not assumed.  Shared by
    features/native_flow.py and features/native_dns.py."""
    if counts.size and int(counts.max()) >= 2**31:
        raise OverflowError(
            f"per-(ip, word) event count {int(counts.max())} exceeds "
            "int32 storage; widen wc_count to int64 before aggregating "
            "inputs this large"
        )
    return counts.astype(np.int32, copy=False)


class NativeLib:
    """Lazy, thread-safe loader for one native .so."""

    def __init__(
        self,
        src_path: str,
        lib_path: str,
        configure: Callable[[ctypes.CDLL], None],
        deps: tuple[str, ...] = (),
    ):
        self._src = os.path.abspath(src_path)
        self._lib_path = lib_path
        self._configure = configure
        self._deps = tuple(os.path.abspath(d) for d in deps)
        self._lock = threading.Lock()
        self._lib: ctypes.CDLL | None = None
        self._failed = False
        # "unloaded" until load() runs; then how the library was
        # obtained: "prebuilt" (an existing .so was used), "built"
        # (compiled by this process), or "python-fallback" (neither
        # worked, or ONI_ML_TPU_NO_NATIVE forced it).
        self.status = "unloaded"
        _LIBRARIES.append(self)

    @property
    def name(self) -> str:
        return os.path.basename(self._lib_path)

    def _stale(self) -> bool:
        try:
            lib_mtime = os.path.getmtime(self._lib_path)
            return any(
                os.path.getmtime(f) > lib_mtime
                for f in (self._src, *self._deps)
                if os.path.exists(f)
            )
        except OSError:
            return False

    def _build(self) -> bool:
        if not os.path.exists(self._src):
            return False
        os.makedirs(os.path.dirname(self._lib_path), exist_ok=True)
        tmp = self._lib_path + f".build{os.getpid()}"
        cmd = [
            "g++", "-O2", "-std=c++17", "-fPIC", "-shared",
            # Match CPython's unfused float arithmetic bit-for-bit
            # (the parity tests assert exact equality on entropy etc.).
            "-ffp-contract=off",
            # The featurizers' parallel ingest/finish paths spawn
            # std::threads; harmless for the thread-free modules.
            "-pthread",
            "-o", tmp, self._src,
        ]
        try:
            subprocess.run(cmd, check=True, capture_output=True, timeout=120)
            # Atomic: concurrent builders don't collide.
            os.replace(tmp, self._lib_path)
        except (OSError, subprocess.SubprocessError):
            if os.path.exists(tmp):
                os.remove(tmp)
            return False
        return True

    def load(self) -> ctypes.CDLL | None:
        if self._lib is not None or self._failed:
            return self._lib
        with self._lock:
            if self._lib is not None or self._failed:
                return self._lib
            if os.environ.get("ONI_ML_TPU_NO_NATIVE"):
                return self._fail()
            self.status = "prebuilt"
            if not os.path.exists(self._lib_path) or self._stale():
                if self._build():
                    self.status = "built"
                elif not os.path.exists(self._lib_path):
                    return self._fail()
            return self._load_configured()

    def _fail(self) -> None:
        """Record the fallback.  Caller holds self._lock."""
        self._failed = True
        self.status = "python-fallback"
        return None

    def _load_configured(self) -> ctypes.CDLL | None:
        """CDLL + configure with one rebuild retry.  The retry loads
        from a COPY at a unique temp path: glibc's dlopen matches
        already-loaded objects by name string, so re-CDLL'ing
        self._lib_path after os.replace would hand back the same stale
        handle that just failed (round-3 advisor finding).  Caller
        holds self._lock."""
        load_path = self._lib_path
        try:
            for attempt in (0, 1):
                try:
                    lib = ctypes.CDLL(load_path)
                    self._configure(lib)
                    self._lib = lib
                    return self._lib
                except OSError:
                    return self._fail()
                except AttributeError:
                    # A prebuilt .so missing a newly added export even
                    # though mtimes looked fresh (copied binary, touch,
                    # clock skew).  One rebuild usually fixes it; if
                    # the toolchain is absent (or the symbol name is
                    # simply wrong in configure), warn and degrade to
                    # the Python fallback instead of crashing callers.
                    if attempt == 0 and self._build():
                        import shutil
                        import tempfile

                        try:
                            fd, load_path = tempfile.mkstemp(
                                suffix=".so",
                                prefix=os.path.basename(self._lib_path)
                                + ".",
                            )
                            os.close(fd)
                            shutil.copy2(self._lib_path, load_path)
                            self.status = "built"
                            continue
                        except OSError:
                            pass  # full/RO tempdir: degrade, don't raise
                    import warnings

                    warnings.warn(
                        f"{self._lib_path}: native symbol configuration "
                        "failed after rebuild attempt; using the Python "
                        "fallback paths"
                    )
                    return self._fail()
            return self._fail()
        finally:
            if load_path != self._lib_path:
                # Linux keeps the mapping alive after unlink; don't
                # leave rebuild copies behind in the tempdir.
                try:
                    os.unlink(load_path)
                except OSError:
                    pass

    def available(self) -> bool:
        return self.load() is not None


def load_all() -> "dict[str, str]":
    """Load every native library the package has and return
    {library file name: status}.  Imports the four wrapper modules so
    their NativeLib instances exist; loading builds from native_src/
    where no usable .so is on disk."""
    from . import native_emit  # noqa: F401
    from .features import native_dns, native_flow  # noqa: F401
    from .io import native  # noqa: F401

    for lib in _LIBRARIES:
        lib.load()
    return {lib.name: lib.status for lib in _LIBRARIES}
