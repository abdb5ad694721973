"""ctypes bindings for the native SPF core (native/spfcore.cpp).

The shared library is compiled from the tracked source on first use and
stored under the SHA-256 of that source and of the compile command, so a
library is only ever loaded if it was built from exactly the source in
this checkout: a stale copy left on disk by another commit can never be
picked up. ``build()`` compiles unconditionally (what ``make native`` and
``chip_smoke.py`` call).

A machine without a C++ compiler has no native core: ``is_available()``
says so once, loudly, and callers that can (the KSP2 tracer, Decision's
fallback rung) use the Python paths. A compiler that is present and
FAILS is a defect in the tree and raises ``NativeBuildError`` with the
compiler's message wherever the native core was asked for.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import shutil
import subprocess
import threading
from typing import Optional, Tuple

import numpy as np

log = logging.getLogger(__name__)

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
_SRC = os.path.join(_REPO_ROOT, "native", "spfcore.cpp")
_CXX = "g++"
_CXXFLAGS = ("-O3", "-std=c++17", "-shared", "-fPIC", "-pthread")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_no_compiler = False


class NativeBuildError(RuntimeError):
    """The native core could not be built or loaded."""


def lib_path() -> str:
    """Where the library built from the current source lives."""
    h = hashlib.sha256(" ".join((_CXX,) + _CXXFLAGS).encode())
    with open(_SRC, "rb") as f:
        h.update(f.read())
    return os.path.join(
        _REPO_ROOT, "native", f"libspfcore-{h.hexdigest()[:16]}.so"
    )


def build() -> str:
    """Compile native/spfcore.cpp now; returns the library path. Raises
    ``NativeBuildError`` when the compiler is missing or fails."""
    out = lib_path()
    # build beside the target and rename: a concurrent loader never
    # sees a half-written library
    tmp = f"{out}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            [_CXX, *_CXXFLAGS, _SRC, "-o", tmp],
            check=True,
            capture_output=True,
            text=True,
            timeout=300,
        )
        os.replace(tmp, out)
    except FileNotFoundError as exc:
        raise NativeBuildError(f"no C++ compiler: {exc}") from exc
    except subprocess.CalledProcessError as exc:
        raise NativeBuildError(
            f"{_CXX} failed on {_SRC} (exit {exc.returncode}):\n"
            f"{exc.stderr}"
        ) from exc
    except subprocess.TimeoutExpired as exc:
        raise NativeBuildError(f"{_CXX} timed out on {_SRC}") from exc
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return out


def _load() -> Optional[ctypes.CDLL]:
    """The bound library, or None on a machine with no compiler."""
    global _lib, _no_compiler
    with _lock:
        if _lib is not None:
            return _lib
        if _no_compiler:
            return None
        path = lib_path()
        if not os.path.exists(path):
            if shutil.which(_CXX) is None:
                _no_compiler = True
                log.error(
                    "native SPF core unavailable: no %s on PATH; the "
                    "KSP2 tracer and the Decision fallback rung run in "
                    "Python", _CXX,
                )
                return None
            build()
        try:
            lib = ctypes.CDLL(path)
        except OSError as exc:
            raise NativeBuildError(f"cannot load {path}: {exc}") from exc
        i32p = ctypes.POINTER(ctypes.c_int32)
        u8p = ctypes.POINTER(ctypes.c_uint8)
        lib.spf_from_sources.argtypes = [
            ctypes.c_int32, ctypes.c_int32, i32p, i32p, i32p, u8p,
            i32p, ctypes.c_int32, ctypes.c_int32, i32p,
        ]
        lib.spf_all_pairs.argtypes = [
            ctypes.c_int32, ctypes.c_int32, i32p, i32p, i32p, u8p,
            ctypes.c_int32, i32p,
        ]
        lib.spf_first_hops.argtypes = [
            ctypes.c_int32, ctypes.c_int32, i32p, i32p, i32p, u8p,
            ctypes.c_int32, i32p, i32p, u8p,
        ]
        lib.ksp2_trace_batch.argtypes = [
            ctypes.c_int32, ctypes.c_int32, i32p, i32p, i32p, i32p,
            ctypes.c_int32, u8p, ctypes.c_int32, i32p, i32p,
            ctypes.c_int32, i32p, i32p, i32p, ctypes.c_int32, i32p,
        ]
        lib.ksp2_trace_batch.restype = ctypes.c_int32
        _lib = lib
        return _lib


def is_available() -> bool:
    return _load() is not None


def _as_i32p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))


def _as_u8p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def _edge_arrays(snap) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    srcs, dsts, weights = [], [], []
    for links in snap.links_from:
        for dl in links:
            srcs.append(dl.src_id)
            dsts.append(dl.dst_id)
            weights.append(dl.metric)
    return (
        np.asarray(srcs, dtype=np.int32),
        np.asarray(dsts, dtype=np.int32),
        np.asarray(weights, dtype=np.int32),
    )


def all_pairs_distances(snap, n_threads: int = 0) -> Optional[np.ndarray]:
    """All-sources distances over a GraphSnapshot via the native core.
    Returns None when the native library is unavailable."""
    lib = _load()
    if lib is None:
        return None
    n = snap.n
    srcs, dsts, weights = _edge_arrays(snap)
    overloaded = np.ascontiguousarray(
        snap.overloaded[:n].astype(np.uint8)
    )
    out = np.empty((n, n), dtype=np.int32)
    if n_threads <= 0:
        n_threads = min(16, os.cpu_count() or 1)
    lib.spf_all_pairs(
        n, len(srcs), _as_i32p(srcs), _as_i32p(dsts), _as_i32p(weights),
        _as_u8p(overloaded), n_threads, _as_i32p(out),
    )
    return out


def first_hop_matrix(
    snap, src_id: int, dist_src: np.ndarray, dist_all: np.ndarray
) -> Optional[np.ndarray]:
    lib = _load()
    if lib is None:
        return None
    n = snap.n
    srcs, dsts, weights = _edge_arrays(snap)
    overloaded = np.ascontiguousarray(snap.overloaded[:n].astype(np.uint8))
    dist_src = np.ascontiguousarray(dist_src[:n].astype(np.int32))
    dist_all = np.ascontiguousarray(dist_all[:n, :n].astype(np.int32))
    out = np.zeros((n, n), dtype=np.uint8)
    lib.spf_first_hops(
        n, len(srcs), _as_i32p(srcs), _as_i32p(dsts), _as_i32p(weights),
        _as_u8p(overloaded), src_id, _as_i32p(dist_src), _as_i32p(dist_all),
        _as_u8p(out),
    )
    return out


def trace_batch(
    n: int,
    n_links: int,
    cand_off: np.ndarray,
    cand_link: np.ndarray,
    cand_uid: np.ndarray,
    cand_w: np.ndarray,
    src: int,
    transit_blocked: np.ndarray,
    dst_ids: np.ndarray,
    rows: np.ndarray,
    shared_row: bool,
    excl_off: np.ndarray,
    excl_ids: np.ndarray,
    reach: Optional[np.ndarray] = None,
) -> Optional[list]:
    """Batched KSP2 link-disjoint path enumeration via the native core
    (spfcore.cpp ksp2_trace_batch) — byte-identical path content and
    order to ksp2_engine.trace_paths_from_row. Returns a list (one per
    destination) of lists of link-id paths, or None when the native
    library is unavailable. The int32 output buffer grows on overflow.
    ``reach``: a C-contiguous int32 [len(dst_ids), n] set to -1; for
    every node a destination's traces consulted the core leaves how
    far down its candidate list the searches that found a path
    looked (low 14 bits: 1 + the position of the last candidate
    examined; bit 14 where one ran the list out) and bit 15 where the
    last search, which found none, reached the node (spfcore.cpp's
    comment has why they are kept apart)."""
    lib = _load()
    if lib is None:
        return None
    n_dsts = len(dst_ids)
    cap = max(4096, 2 * n_links + 64 * n_dsts)
    while True:
        out = np.empty(cap, dtype=np.int32)
        wrote = lib.ksp2_trace_batch(
            n, n_links, _as_i32p(cand_off), _as_i32p(cand_link),
            _as_i32p(cand_uid), _as_i32p(cand_w), src,
            _as_u8p(transit_blocked), n_dsts, _as_i32p(dst_ids),
            _as_i32p(rows), 1 if shared_row else 0,
            _as_i32p(excl_off), _as_i32p(excl_ids), _as_i32p(out), cap,
            None if reach is None else _as_i32p(reach),
        )
        if wrote >= 0:
            break
        cap *= 4
    # one bulk conversion: indexing a numpy array an element at a time
    # costs more than the trace itself at a few thousand paths
    flat = out[:wrote].tolist()
    result = []
    pos = 0
    for _ in range(n_dsts):
        n_paths = flat[pos]
        pos += 1
        paths = []
        for _p in range(n_paths):
            ln = flat[pos]
            pos += 1
            paths.append(flat[pos : pos + ln])
            pos += ln
        result.append(paths)
    return result
