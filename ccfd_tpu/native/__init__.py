"""Native (C++) hot-path bindings with a transparent numpy fallback.

Builds ``decode.cpp`` / ``log.cpp`` / ``httpfront.cpp`` with g++ on first
import (cached next to the sources), loads the result via ctypes, and
exposes:

- ``decode_csv(data: bytes, n_features) -> (np.ndarray (B, F) f32, bad_rows)``
- ``pad_batch(x, bucket_rows) -> np.ndarray (bucket, F) f32``

If no toolchain is available the numpy implementations (identical
semantics, asserted by tests/test_native.py) are used — the framework never
hard-requires a compiler at runtime.

The library is always the one built from the sources on disk for the
machine it runs on: its file name carries a digest of the three sources,
the compiler flags and — because the default ``-march=native`` means "this
CPU" — the host CPU's identity. A ``.so`` copied in from another machine,
or left over from before a source edit, has another name and is never
loaded while the sources are present.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import platform
import subprocess
import threading

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRCS = [
    os.path.join(_HERE, "decode.cpp"),
    os.path.join(_HERE, "log.cpp"),
    os.path.join(_HERE, "httpfront.cpp"),
]
# the un-digested name: only a package shipped WITHOUT its sources loads it
_SO = os.path.join(_HERE, "_ccfd_native.so")

_lib = None
_lib_lock = threading.Lock()
_build_failed = False


def _host_fingerprint() -> str:
    """Short stable id for this host's CPU: ``-march=native`` machine code
    built on one CPU can SIGILL on another, so the id is part of the
    library's name whenever the flags say ``native``."""
    material = platform.machine()
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    material += line
                    break
    except OSError:
        material += platform.processor()
    return hashlib.sha256(material.encode()).hexdigest()[:12]


def _flags() -> list[str]:
    # CCFD_NATIVE_MARCH overrides the target microarchitecture: container
    # images built on one CPU and deployed to another must NOT bake the
    # builder's -march=native (a zmm-tuned .so can SIGILL on the deploy
    # node) — e.g. x86-64-v3 is the portable-with-AVX2 choice
    march = os.environ.get("CCFD_NATIVE_MARCH", "native")
    return ["-O3", f"-march={march}", "-shared", "-fPIC", "-pthread"]


def _so_path(flags: list[str]) -> str:
    """Where the library built from the present sources with ``flags`` on
    this host lives: next to ``_SO``, named by the digest of everything the
    machine code depends on."""
    h = hashlib.sha256()
    for src in _SRCS:
        with open(src, "rb") as f:
            h.update(f.read())
    h.update(" ".join(flags).encode())
    if "-march=native" in flags:
        h.update(_host_fingerprint().encode())
    return os.path.join(os.path.dirname(_SO),
                        f"_ccfd_native.{h.hexdigest()[:16]}.so")


def _build() -> str | None:
    if not all(os.path.exists(s) for s in _SRCS):
        # sources (partially) stripped: a rebuild is impossible, so trust
        # the shipped .so; with neither, the numpy fallback
        return _SO if os.path.exists(_SO) else None
    flags = _flags()
    so = _so_path(flags)
    if os.path.exists(so):
        return so
    tmp = f"{so}.{os.getpid()}.tmp"  # concurrent importers race to publish
    try:
        subprocess.run(
            ["g++", *flags, *_SRCS, "-o", tmp],
            check=True,
            capture_output=True,
            timeout=120,
        )
        # ccfd-lint: disable=durability-seam -- a build output, rebuilt from source whenever absent; the rename only keeps a concurrent importer from loading a half-written file
        os.replace(tmp, so)
        return so
    except (OSError, subprocess.SubprocessError):
        return None


def _load():
    global _lib, _build_failed
    with _lib_lock:
        if _lib is not None or _build_failed:
            return _lib
        path = _build()
        if path is None:
            _build_failed = True
            return None
        try:
            lib = ctypes.CDLL(path)
        except OSError:
            # a .so that won't load here (a shipped one on a mismatched
            # deploy node, a torn file): rebuild from sources when
            # possible, else degrade to the numpy paths — never hard-fail
            # the caller
            try:
                os.remove(path)
            except OSError:
                pass
            path = _build()
            if path is None:
                _build_failed = True
                return None
            try:
                lib = ctypes.CDLL(path)
            except OSError:
                _build_failed = True
                return None
        lib.ccfd_decode_csv.restype = ctypes.c_int
        lib.ccfd_decode_csv.argtypes = [
            ctypes.c_char_p,
            ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_float),
            ctypes.c_int,
            ctypes.c_int,
            ctypes.POINTER(ctypes.c_int),
        ]
        lib.ccfd_decode_ndarray.restype = ctypes.c_int
        lib.ccfd_decode_ndarray.argtypes = [
            ctypes.c_char_p,
            ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_float),
            ctypes.c_int,
            ctypes.c_int,
            ctypes.POINTER(ctypes.c_int),
        ]
        lib.ccfd_pad_batch.restype = None
        lib.ccfd_pad_batch.argtypes = [
            ctypes.POINTER(ctypes.c_float),
            ctypes.c_int,
            ctypes.c_int,
            ctypes.POINTER(ctypes.c_float),
            ctypes.c_int,
        ]
        lib.ccfd_front_create.restype = ctypes.c_void_p
        lib.ccfd_front_create.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_int),
        ]
        lib.ccfd_front_take.restype = ctypes.c_int
        lib.ccfd_front_take.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_float), ctypes.c_int,
            ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_double),
            ctypes.c_int, ctypes.c_int,
        ]
        lib.ccfd_front_respond.restype = None
        lib.ccfd_front_respond.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int),
            ctypes.POINTER(ctypes.c_int), ctypes.c_int,
            ctypes.POINTER(ctypes.c_float), ctypes.c_char_p,
        ]
        lib.ccfd_front_take_misc.restype = ctypes.c_int
        lib.ccfd_front_take_misc.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int, ctypes.c_char_p,
            ctypes.c_int, ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_int), ctypes.c_int,
        ]
        lib.ccfd_front_free.restype = None
        lib.ccfd_front_free.argtypes = [ctypes.c_void_p]
        lib.ccfd_front_respond_misc.restype = None
        lib.ccfd_front_respond_misc.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_int, ctypes.c_char_p,
            ctypes.c_char_p, ctypes.c_int,
        ]
        lib.ccfd_front_stats.restype = None
        lib.ccfd_front_stats.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_long)
        ]
        lib.ccfd_front_set_host_model.restype = None
        lib.ccfd_front_set_host_model.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.POINTER(ctypes.c_int),
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_float),
            ctypes.c_int, ctypes.c_char_p, ctypes.POINTER(ctypes.c_int),
        ]
        # newer symbol: a shipped pre-q8 .so loaded via the trust path
        # (sources stripped) must degrade to "q8 pusher unavailable",
        # not hard-fail every native entry point
        if hasattr(lib, "ccfd_front_set_host_q8_model"):
            lib.ccfd_front_set_host_q8_model.restype = None
            lib.ccfd_front_set_host_q8_model.argtypes = [
                ctypes.c_void_p, ctypes.c_int, ctypes.POINTER(ctypes.c_int),
                ctypes.POINTER(ctypes.c_float),
                ctypes.POINTER(ctypes.c_float),
                ctypes.POINTER(ctypes.c_float),
                ctypes.POINTER(ctypes.c_float),
                ctypes.POINTER(ctypes.c_float),
                ctypes.c_int, ctypes.c_char_p, ctypes.POINTER(ctypes.c_int),
            ]
        lib.ccfd_front_set_host_trees.restype = None
        lib.ccfd_front_set_host_trees.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_float), ctypes.c_float, ctypes.c_int,
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_int),
        ]
        lib.ccfd_front_set_latency_buckets.restype = None
        lib.ccfd_front_set_latency_buckets.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_double), ctypes.c_int,
        ]
        lib.ccfd_front_host_stats.restype = ctypes.c_long
        lib.ccfd_front_host_stats.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_long),
            ctypes.POINTER(ctypes.c_double), ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_double),
        ]
        lib.ccfd_front_stop.restype = None
        lib.ccfd_front_stop.argtypes = [ctypes.c_void_p]
        lib.ccfd_front_destroy.restype = None
        lib.ccfd_front_destroy.argtypes = [ctypes.c_void_p]
        lib.ccfd_log_frame.restype = ctypes.c_size_t
        lib.ccfd_log_frame.argtypes = [
            ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_uint32),
            ctypes.c_int,
            ctypes.POINTER(ctypes.c_uint8),
        ]
        lib.ccfd_log_scan.restype = ctypes.c_int
        lib.ccfd_log_scan.argtypes = [
            ctypes.c_char_p,
            ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint32),
            ctypes.c_int,
            ctypes.POINTER(ctypes.c_size_t),
        ]
        _lib = lib
        return _lib


def native_available() -> bool:
    return _load() is not None


# ---------------------------------------------------------------------------
# numpy reference implementations (identical semantics)


def _decode_csv_numpy(data: bytes, n_features: int) -> tuple[np.ndarray, int]:
    lines = data.decode("utf-8", errors="replace").splitlines()
    out = np.zeros((len(lines), n_features), np.float32)
    bad = 0
    for i, line in enumerate(lines):
        parts = line.split(",")
        if len(parts) != n_features:
            bad += 1
            continue
        try:
            out[i] = [float(p) for p in parts]
        except ValueError:
            out[i] = 0.0
            bad += 1
    return out, bad


def decode_csv(data: bytes, n_features: int = 30) -> tuple[np.ndarray, int]:
    """Newline-separated CSV float rows -> ((B, F) float32, #bad rows)."""
    if not data:
        return np.zeros((0, n_features), np.float32), 0
    lib = _load()
    if lib is None:
        return _decode_csv_numpy(data, n_features)
    max_rows = data.count(b"\n") + (0 if data.endswith(b"\n") else 1)
    out = np.zeros((max_rows, n_features), np.float32)
    bad = ctypes.c_int(0)
    rows = lib.ccfd_decode_csv(
        data,
        len(data),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        max_rows,
        n_features,
        ctypes.byref(bad),
    )
    return out[:rows], int(bad.value)


def decode_ndarray_json(
    body: bytes, n_features: int = 30, max_rows: int = 1 << 16
) -> np.ndarray | None:
    """Parse a canonical Seldon predict payload's ``data.ndarray`` matrix
    (reference request shape README.md:454-459) natively into (B, F)
    float32. Returns None when the payload needs the Python JSON path — a
    ``names`` key (column remapping), non-numeric cells, rows wider than
    the schema, oversize batches, malformed JSON, or no native toolchain.
    Short rows zero-pad, matching the Python decoder's semantics."""
    lib = _load()
    if lib is None or not body:
        return None
    # '[' count bounds the row count tightly (outer bracket + one per row),
    # so the scratch buffer is sized to the request, not the global cap
    max_rows = min(max_rows, body.count(b"["))
    if max_rows <= 0:
        return None
    out = np.empty((max_rows, n_features), np.float32)
    width = ctypes.c_int(0)
    rows = lib.ccfd_decode_ndarray(
        body,
        len(body),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        max_rows,
        n_features,
        ctypes.byref(width),
    )
    if rows < 0:
        return None
    return out[:rows]


def frame_records(payloads: list[bytes]) -> bytes:
    """Frame payloads as ``[u32 len][u32 crc32][payload]...`` (one buffer)."""
    if not payloads:
        return b""
    lib = _load()
    if lib is None:
        import binascii
        import struct

        parts = []
        for p in payloads:
            parts.append(struct.pack("<II", len(p), binascii.crc32(p)))
            parts.append(p)
        return b"".join(parts)
    concat = b"".join(payloads)
    lens = (ctypes.c_uint32 * len(payloads))(*[len(p) for p in payloads])
    out = ctypes.create_string_buffer(len(concat) + 8 * len(payloads))
    n = lib.ccfd_log_frame(
        concat, lens, len(payloads), ctypes.cast(out, ctypes.POINTER(ctypes.c_uint8))
    )
    return out.raw[:n]


def scan_records(buf: bytes) -> tuple[list[bytes], int, bool]:
    """Replay a segment buffer -> (payloads, valid_prefix_len, corrupt).

    Stops at the first torn or corrupt frame; ``valid_prefix_len`` is where
    a recovering writer should truncate. ``corrupt`` distinguishes a bad
    CRC / insane length from a clean partial tail.
    """
    lib = _load()
    if lib is None:
        return _scan_records_py(buf)
    out: list[bytes] = []
    pos = 0
    corrupt = False
    chunk = 4096
    offs = (ctypes.c_uint64 * chunk)()
    lens = (ctypes.c_uint32 * chunk)()
    consumed = ctypes.c_size_t(0)
    # one buffer copy up front, then chunked scans by pointer offset —
    # re-slicing bytes per chunk would make large-segment replay O(n^2)
    base = ctypes.create_string_buffer(buf, len(buf))
    addr = ctypes.addressof(base)
    while pos < len(buf):
        n = lib.ccfd_log_scan(
            ctypes.c_char_p(addr + pos), len(buf) - pos, offs, lens, chunk,
            ctypes.byref(consumed),
        )
        got = n if n >= 0 else -n - 1  # corruption encodes -(valid+1)
        for i in range(got):
            off = pos + offs[i]
            out.append(buf[off : off + lens[i]])
        pos += consumed.value
        if n < 0:
            corrupt = True
            break
        if n < chunk:  # clean end (EOF or partial tail)
            break
    return out, pos, corrupt


def _scan_records_py(buf: bytes) -> tuple[list[bytes], int, bool]:
    import binascii
    import struct

    out: list[bytes] = []
    pos = 0
    while pos + 8 <= len(buf):
        plen, want = struct.unpack_from("<II", buf, pos)
        if plen > 1 << 30:
            return out, pos, True
        if pos + 8 + plen > len(buf):
            break
        payload = buf[pos + 8 : pos + 8 + plen]
        if binascii.crc32(payload) != want:
            return out, pos, True
        out.append(payload)
        pos += 8 + plen
    return out, pos, False


def pad_batch(x: np.ndarray, bucket_rows: int) -> np.ndarray:
    """(n, F) -> (bucket_rows, F) zero-padded float32 (truncates if larger)."""
    x = np.ascontiguousarray(x, np.float32)
    lib = _load()
    if lib is None:
        out = np.zeros((bucket_rows, x.shape[1]), np.float32)
        out[: min(len(x), bucket_rows)] = x[:bucket_rows]
        return out
    out = np.empty((bucket_rows, x.shape[1]), np.float32)
    lib.ccfd_pad_batch(
        x.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        x.shape[0],
        x.shape[1],
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        bucket_rows,
    )
    return out
