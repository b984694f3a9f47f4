"""Native (C++) runtime components: the JSONL record loader.

The reference's data layer bottoms out in native code too — `datasets.
load_dataset('json')` (reference train-torchrun.py:153-159) runs Arrow's
C++ JSON reader.  Here the equivalent is ``jsonl_loader.cc``: a C++ parser
for line-delimited JSON records, compiled on demand with the toolchain's
g++ into ``_jsonl.<source hash>.so`` next to this file, consumed through a zero-copy
ctypes view.  ``data/dataset.py`` routes large JSONL files through it and
keeps the pure-Python ``json.loads`` path as the always-available fallback
(``available()`` gates every use).

Record values that are JSON strings are unescaped in C++; anything else
(numbers, bools, null, nested values) arrives as raw JSON text and is
parsed by ``json.loads`` only when that field is actually read.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import json
import os
import subprocess
import threading
from typing import Iterator, Sequence

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "jsonl_loader.cc")

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_build_error: str | None = None
_compiled_here = False


class _DllmJsonl(ctypes.Structure):
    _fields_ = [
        ("parsed", ctypes.c_void_p),
        ("n_records", ctypes.c_int64),
        ("n_fields", ctypes.c_int64),
        ("arena", ctypes.c_void_p),
        ("arena_len", ctypes.c_int64),
        ("rec_start", ctypes.POINTER(ctypes.c_int64)),
        ("key_off", ctypes.POINTER(ctypes.c_int64)),
        ("key_len", ctypes.POINTER(ctypes.c_int64)),
        ("val_off", ctypes.POINTER(ctypes.c_int64)),
        ("val_len", ctypes.POINTER(ctypes.c_int64)),
        ("kind", ctypes.POINTER(ctypes.c_int8)),
        ("error", ctypes.c_char_p),
    ]


def _so_path() -> str:
    """The library's name carries the hash of the source it was built
    from, so a library from an older source — or one that arrived with
    the tree from somewhere else — is never mistaken for this checkout's."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_DIR, f"_jsonl.{digest}.so")


def _build(so: str) -> str | None:
    """Compile the shared library if needed; returns an error string or None.

    Compiles to a per-process temp name and renames into place: the rename
    is atomic, so concurrent builders race harmlessly and an interrupted
    build can never leave a truncated library under the final name."""
    global _compiled_here
    if os.path.exists(so):
        return None
    tmp = f"{so}.{os.getpid()}.tmp"
    cmd = ["g++", "-std=c++17", "-O2", "-shared", "-fPIC", _SRC, "-o", tmp]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
    except (OSError, subprocess.TimeoutExpired) as e:
        return f"g++ unavailable: {e}"
    if proc.returncode != 0:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return f"g++ failed: {proc.stderr[-500:]}"
    os.replace(tmp, so)
    _compiled_here = True
    for stale in glob.glob(os.path.join(_DIR, "_jsonl*.so")):
        if stale != so:
            try:
                os.unlink(stale)
            except OSError:
                pass
    return None


def _load_lib() -> ctypes.CDLL | None:
    global _lib, _build_error
    with _lock:
        if _lib is not None or _build_error is not None:
            return _lib
        so = _so_path()
        err = _build(so)
        if err is not None:
            _build_error = err
            return None
        try:
            lib = ctypes.CDLL(so)
        except OSError as e:
            _build_error = str(e)
            return None
        lib.dllm_jsonl_load.argtypes = [ctypes.c_char_p]
        lib.dllm_jsonl_load.restype = ctypes.POINTER(_DllmJsonl)
        lib.dllm_jsonl_parse.argtypes = [ctypes.c_char_p, ctypes.c_int64]
        lib.dllm_jsonl_parse.restype = ctypes.POINTER(_DllmJsonl)
        lib.dllm_jsonl_free.argtypes = [ctypes.POINTER(_DllmJsonl)]
        lib.dllm_jsonl_free.restype = None
        _lib = lib
        return _lib


def available() -> bool:
    """True when the native loader compiled and loaded on this machine."""
    return _load_lib() is not None


def build_error() -> str | None:
    """Why ``available()`` is False (None while it's True/untried)."""
    return _build_error


def compiled_here() -> bool:
    """True when THIS process compiled the library from ``jsonl_loader.cc``
    (False: an earlier run of this checkout left the same build)."""
    return _compiled_here


class JsonlRecords(Sequence):
    """Zero-copy lazy view over a parsed JSONL file.

    ``records[i]`` materializes one dict; string fields are decoded
    straight out of the C++ arena, non-string fields go through
    ``json.loads`` of their raw text.  Works as the ``records`` sequence
    the (lazy) datasets consume — nothing is materialized until accessed.
    """

    def __init__(self, handle, lib: ctypes.CDLL):
        self._h = handle
        self._lib = lib
        c = handle.contents
        self._n = int(c.n_records)
        self._arena = (ctypes.c_char * c.arena_len).from_address(c.arena) if c.arena_len else b""

    def __len__(self) -> int:
        return self._n

    def _field(self, j: int) -> tuple[str, object]:
        c = self._h.contents
        # slicing a ctypes char array already yields fresh bytes — no copy
        key = self._arena[c.key_off[j] : c.key_off[j] + c.key_len[j]].decode("utf-8")
        raw = self._arena[c.val_off[j] : c.val_off[j] + c.val_len[j]]
        if c.kind[j] == 0:
            return key, raw.decode("utf-8")
        return key, json.loads(raw)

    def __getitem__(self, i: int | slice) -> dict | list[dict]:
        if isinstance(i, slice):  # list parity: ``serve --num-prompts`` slices
            return [self[j] for j in range(*i.indices(self._n))]
        if i < 0:
            i += self._n  # list-parity negative indexing
        if not 0 <= i < self._n:
            raise IndexError(i)
        c = self._h.contents
        return dict(self._field(j) for j in range(c.rec_start[i], c.rec_start[i + 1]))

    def __iter__(self) -> Iterator[dict]:
        for i in range(self._n):
            yield self[i]

    def __del__(self) -> None:
        h, self._h = getattr(self, "_h", None), None
        if h is not None:
            self._lib.dllm_jsonl_free(h)


def load_jsonl(path: str) -> JsonlRecords:
    """Parse a JSONL file with the native loader.

    Raises ``RuntimeError`` if the loader isn't available (callers gate on
    ``available()``) and ``ValueError`` on malformed input, with the line
    number from the C++ parser.
    """
    lib = _load_lib()
    if lib is None:
        raise RuntimeError(f"native jsonl loader unavailable: {_build_error}")
    handle = lib.dllm_jsonl_load(os.fspath(path).encode())
    if handle.contents.error:
        msg = handle.contents.error.decode()
        lib.dllm_jsonl_free(handle)
        raise ValueError(f"{path}: {msg}")
    return JsonlRecords(handle, lib)
