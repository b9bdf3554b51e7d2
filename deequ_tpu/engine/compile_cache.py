"""Torn-write-safe persistent XLA compilation cache store.

The stock jax file cache writes entries with a plain
``path.write_bytes(value)`` and reads them back with a blind
``read_bytes()``. A process killed mid-write (the crash-isolation
children of engine/subproc.py die by SIGKILL as a matter of course)
leaves a TRUNCATED entry that the next process happily deserializes —
the PR 12 ops note traced ApproxCountDistinct returning garbage
registers to exactly such a poisoned persistent-cache entry.

:class:`SafeCompilationCache` closes both holes:

- **Atomic writes** — ``put`` writes to a temp file in the cache
  directory and ``os.replace``-s it over the final name, so readers
  only ever observe no entry or a complete entry.
- **Validate-on-read** — ``get`` checks the entry actually decompresses
  (jax's value format is ``compress(4-byte compile time + serialized
  executable)``; zstandard when available, zlib otherwise) and meets
  the minimum length before returning it. A short/corrupt entry is
  unlinked and reported as a MISS — one recompile — with an
  ``engine.compile_cache_corrupt`` counter and a
  ``compile_cache_corrupt`` telemetry event, instead of feeding XLA a
  torn executable.
- **Cross-process lock** — an ``fcntl.flock`` on ``<dir>/.deequ_tpu.lock``
  brackets each read-validate-unlink and probe-then-replace sequence,
  so two processes racing the same key can't interleave a validation
  read with a concurrent replace.

:func:`install` swaps this store into jax's module-level cache slot
under jax's own initialization mutex. It is deliberately defensive: if
the (private) internals moved in a newer jax, installation reports
failure and the stock cache stays in place — the cache is an
optimization, never a correctness dependency.
"""

from __future__ import annotations

import os
import tempfile
import zlib
from typing import Optional

try:  # the same optional dependency jax itself compresses with
    import zstandard  # type: ignore
except ImportError:  # pragma: no cover - env without zstandard
    zstandard = None

#: zstd frame magic — distinguishes which codec wrote an entry, so a
#: zlib-written entry from an older process still validates here
_ZSTD_MAGIC = b"\x28\xb5\x2f\xfd"

#: compressed payload smaller than this cannot hold even the 4-byte
#: compile-time header; zlib's minimal stream is 8 bytes
_MIN_ENTRY_BYTES = 8

_LOCK_NAME = ".deequ_tpu.lock"


def _decompress(data: bytes) -> bytes:
    if data[:4] == _ZSTD_MAGIC:
        if zstandard is None:
            raise ValueError("zstd entry but no zstandard module")
        return zstandard.ZstdDecompressor().decompress(data)
    return zlib.decompress(data)


def _validate(data: Optional[bytes]) -> bool:
    """True iff ``data`` is a structurally complete cache entry: long
    enough, decompresses cleanly, and the plaintext holds at least the
    4-byte compile-time header."""
    if data is None or len(data) < _MIN_ENTRY_BYTES:
        return False
    try:
        plain = _decompress(data)
    except Exception:
        return False
    return len(plain) >= 4


class _FileLock:
    """``fcntl.flock`` context manager on a sidecar lock file. On
    platforms without fcntl (or an unlockable directory) it degrades to
    a no-op — atomic replace alone still prevents torn reads within a
    single key."""

    def __init__(self, path: str):
        self._path = path
        self._fd: Optional[int] = None

    def __enter__(self):
        try:
            import fcntl

            self._fd = os.open(self._path, os.O_RDWR | os.O_CREAT, 0o644)
            fcntl.flock(self._fd, fcntl.LOCK_EX)
        except Exception:
            if self._fd is not None:
                os.close(self._fd)
                self._fd = None
        return self

    def __exit__(self, *exc):
        if self._fd is not None:
            try:
                import fcntl

                fcntl.flock(self._fd, fcntl.LOCK_UN)
            except Exception:
                pass
            os.close(self._fd)
            self._fd = None
        return False


class SafeCompilationCache:
    """Duck-typed replacement for jax's file cache (``get``/``put`` +
    the ``_path`` attribute ``reset_cache`` reaches for)."""

    def __init__(self, path: str):
        os.makedirs(path, exist_ok=True)
        self._path = path

    def _entry_path(self, key: str) -> str:
        return os.path.join(self._path, key)

    def _lock(self) -> _FileLock:
        return _FileLock(os.path.join(self._path, _LOCK_NAME))

    def _report_corrupt(self, key: str, size: int) -> None:
        from deequ_tpu.telemetry import get_telemetry

        tm = get_telemetry()
        tm.counter("engine.compile_cache_corrupt").inc()
        tm.event("compile_cache_corrupt", key=key, size_bytes=size)

    def get(self, key: str) -> Optional[bytes]:
        path = self._entry_path(key)
        with self._lock():
            try:
                with open(path, "rb") as f:
                    data = f.read()
            except FileNotFoundError:
                return None
            except OSError:
                return None
            if _validate(data):
                return data
            # torn/corrupt entry: drop it so the recompile's put heals
            # the cache, and surface the event for the ops report
            try:
                os.unlink(path)
            except OSError:
                pass
        self._report_corrupt(key, len(data) if data else 0)
        return None

    def put(self, key: str, value: bytes) -> None:
        path = self._entry_path(key)
        with self._lock():
            try:
                # keep an existing VALID entry (first writer wins, like
                # the stock cache's exists() probe) but let a fresh
                # compile overwrite a corrupt one
                with open(path, "rb") as f:
                    if _validate(f.read()):
                        return
            except OSError:
                pass
            fd, tmp = tempfile.mkstemp(
                dir=self._path, prefix=".tmp-" + key[:32] + "-"
            )
            try:
                with os.fdopen(fd, "wb") as f:
                    f.write(value)
                os.replace(tmp, path)
            except OSError:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass


def install(cache_dir: str) -> bool:
    """Swap :class:`SafeCompilationCache` into jax's module-level cache
    slot (under jax's own init mutex, with the initialized flag set so
    ``_initialize_cache`` never replaces it). Returns False — leaving
    the stock cache in charge — if jax's private internals have moved."""
    try:
        from jax._src import compilation_cache as cc

        with cc._cache_initialized_mutex:
            cc._cache = SafeCompilationCache(cache_dir)
            cc._cache_initialized = True
        return True
    except Exception:
        return False
