"""ctypes bindings for the native (C++/OpenMP) neighbor search + partitioner.

The shared library is built on demand from ``src/`` with ``make`` (g++,
-O3 -fopenmp; no ``-march=native``, so a library built on one machine of
an installation loads on another). A failed build or load raises with the
compiler's output: the numpy implementations are the tests' oracle, not a
silent second path.

No pybind11 in this image, so the ABI is a plain C handle API consumed via
ctypes (see src/neighbor.cpp).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

import numpy as np

from .python_ref import NeighborList, neighbor_list_numpy


class NativeBuildError(RuntimeError):
    """``make`` or ``dlopen`` of the native library failed."""


_SRC_DIR = os.path.join(os.path.dirname(__file__), "src")
# DISTMLIP_TPU_NATIVE_LIB points the loader at an alternate build — the
# sanitizer lane (make asan / make tsan in src/, see the Makefile) loads
# _native_asan.so/_native_tsan.so through this
_LIB_PATH = os.environ.get(
    "DISTMLIP_TPU_NATIVE_LIB",
    os.path.join(os.path.dirname(__file__), "_native.so"))
_lock = threading.Lock()
_lib = None


def _build_and_load():
    """The loaded library, built first if it is missing or older than its
    sources. Raises :class:`NativeBuildError` when it cannot be had."""
    global _lib
    with _lock:
        if _lib is not None:
            return _lib
        srcs = [os.path.join(_SRC_DIR, f) for f in os.listdir(_SRC_DIR)
                if f.endswith((".cpp", "Makefile"))]
        if "DISTMLIP_TPU_NATIVE_LIB" not in os.environ and (
            not os.path.exists(_LIB_PATH) or any(
                os.path.getmtime(s) > os.path.getmtime(_LIB_PATH)
                for s in srcs)
        ):
            try:
                subprocess.run(
                    ["make", "-s", "-C", _SRC_DIR],
                    check=True,
                    capture_output=True,
                    text=True,
                )
            except FileNotFoundError as e:
                raise NativeBuildError(
                    f"cannot build {_LIB_PATH}: {e}") from e
            except subprocess.CalledProcessError as e:
                raise NativeBuildError(
                    f"building {_LIB_PATH} failed (make exit "
                    f"{e.returncode}):\n{e.stderr}") from e
        try:
            lib = ctypes.CDLL(_LIB_PATH)
        except OSError as e:
            raise NativeBuildError(f"cannot load {_LIB_PATH}: {e}") from e
        lib.dm_neighbor_build.restype = ctypes.c_void_p
        lib.dm_neighbor_build.argtypes = [
            ctypes.c_int64,
            ctypes.POINTER(ctypes.c_double),
            ctypes.POINTER(ctypes.c_double),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.c_double,
            ctypes.c_double,
            ctypes.c_double,
            ctypes.c_int,
        ]
        lib.dm_neighbor_num_edges.restype = ctypes.c_int64
        lib.dm_neighbor_num_edges.argtypes = [ctypes.c_void_p]
        lib.dm_neighbor_copy.restype = None
        lib.dm_neighbor_copy.argtypes = [ctypes.c_void_p] + [
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_double),
            ctypes.POINTER(ctypes.c_uint8),
            ctypes.POINTER(ctypes.c_double),
            ctypes.POINTER(ctypes.c_int64),
        ]
        lib.dm_neighbor_free.restype = None
        lib.dm_neighbor_free.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


def resolve_num_threads() -> int:
    """Single source of truth for the host-thread knob (0 = all cores)."""
    return int(os.environ.get("DISTMLIP_TPU_NUM_THREADS",
                              os.environ.get("DISTMLIP_NUM_THREADS", 0)))


def _ptr(arr, ctype):
    return arr.ctypes.data_as(ctypes.POINTER(ctype))


def neighbor_list(
    cart, lattice, pbc, r: float, bond_r: float = 0.0, tol: float = 1e-8,
    num_threads: int | None = None,
) -> NeighborList:
    """Periodic neighbor search through the native library.

    Threads resolve as: explicit arg > DISTMLIP_TPU_NUM_THREADS >
    DISTMLIP_NUM_THREADS > 0 (= OpenMP default, all cores). The env-var knob
    mirrors the reference (pes.py:65-66).
    """
    if np.asarray(cart).shape[0] == 0:
        # the native handle API rejects an empty system
        return neighbor_list_numpy(cart, lattice, pbc, r, bond_r, tol)
    lib = _build_and_load()
    if num_threads is None:
        num_threads = resolve_num_threads()
    cart = np.ascontiguousarray(cart, dtype=np.float64)
    lattice = np.ascontiguousarray(lattice, dtype=np.float64)
    pbc_arr = np.ascontiguousarray(np.asarray(pbc, dtype=np.int64))
    n = cart.shape[0]
    handle = lib.dm_neighbor_build(
        n, _ptr(cart, ctypes.c_double), _ptr(lattice, ctypes.c_double),
        _ptr(pbc_arr, ctypes.c_int64), float(r), float(bond_r), float(tol),
        int(num_threads),
    )
    if not handle:
        raise RuntimeError("native neighbor search failed (empty system or r<=0)")
    try:
        ne = lib.dm_neighbor_num_edges(handle)
        src = np.empty(ne, dtype=np.int64)
        dst = np.empty(ne, dtype=np.int64)
        offsets = np.empty((ne, 3), dtype=np.int32)
        distances = np.empty(ne, dtype=np.float64)
        bond_mask = np.empty(ne, dtype=np.uint8)
        wrapped = np.empty((n, 3), dtype=np.float64)
        shift = np.empty((n, 3), dtype=np.int64)
        lib.dm_neighbor_copy(
            handle, _ptr(src, ctypes.c_int64), _ptr(dst, ctypes.c_int64),
            _ptr(offsets, ctypes.c_int32), _ptr(distances, ctypes.c_double),
            _ptr(bond_mask, ctypes.c_uint8), _ptr(wrapped, ctypes.c_double),
            _ptr(shift, ctypes.c_int64),
        )
    finally:
        lib.dm_neighbor_free(handle)
    return NeighborList(src, dst, offsets, distances, bond_mask.astype(bool), wrapped, shift)


# ---------------------------------------------------------------------------
# Native partitioner bindings (partition.cpp)
# ---------------------------------------------------------------------------

def _partition_symbols(lib):
    if getattr(lib, "_partition_ready", False):
        return lib
    lib.dm_partition_build.restype = ctypes.c_void_p
    lib.dm_partition_build.argtypes = [
        ctypes.c_int64, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_double), ctypes.POINTER(ctypes.c_double),
        ctypes.c_int64, ctypes.POINTER(ctypes.c_uint8), ctypes.c_int, ctypes.c_int,
    ]
    lib.dm_partition_err.restype = ctypes.c_int
    lib.dm_partition_err.argtypes = [ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64)]
    lib.dm_partition_sizes.restype = None
    lib.dm_partition_sizes.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                       ctypes.POINTER(ctypes.c_int64)]
    lib.dm_partition_copy.restype = None
    lib.dm_partition_copy.argtypes = [ctypes.c_void_p, ctypes.c_int64] + [
        ctypes.POINTER(ctypes.c_int64)
    ] * 12
    lib.dm_partition_free.restype = None
    lib.dm_partition_free.argtypes = [ctypes.c_void_p]
    lib._partition_ready = True
    return lib


def native_partition(src, dst, frac_axis, walls, num_partitions, bond_mask,
                     use_bond_graph, num_threads=None):
    """Run the native partitioner; returns per-partition dict arrays.

    Raises RuntimeError with the offending node on a multi-destination
    border node (same condition the numpy oracle raises PartitionError
    for).
    """
    lib = _partition_symbols(_build_and_load())
    if num_threads is None:
        num_threads = resolve_num_threads()
    src = np.ascontiguousarray(src, dtype=np.int64)
    dst = np.ascontiguousarray(dst, dtype=np.int64)
    frac_axis = np.ascontiguousarray(frac_axis, dtype=np.float64)
    walls = np.ascontiguousarray(walls, dtype=np.float64)
    bm = np.ascontiguousarray(
        bond_mask if bond_mask is not None else np.zeros(len(src), bool),
        dtype=np.uint8,
    )
    n, ne, P = len(frac_axis), len(src), int(num_partitions)
    h = lib.dm_partition_build(
        n, ne, _ptr(src, ctypes.c_int64), _ptr(dst, ctypes.c_int64),
        _ptr(frac_axis, ctypes.c_double), _ptr(walls, ctypes.c_double),
        P, _ptr(bm, ctypes.c_uint8), int(bool(use_bond_graph)), int(num_threads),
    )
    try:
        err_node = ctypes.c_int64(-1)
        err = lib.dm_partition_err(h, ctypes.byref(err_node))
        if err != 0:
            raise RuntimeError(
                f"native partitioner: node {err_node.value} reaches multiple "
                f"partitions (code {err}); reduce num_partitions"
            )
        out = []
        null = ctypes.POINTER(ctypes.c_int64)()
        for p in range(P):
            sizes = np.zeros(5, dtype=np.int64)
            lib.dm_partition_sizes(h, p, _ptr(sizes, ctypes.c_int64))
            nn, nee, nb, nl, nm = map(int, sizes)
            d = {
                "global_ids": np.empty(nn, np.int64),
                "node_markers": np.empty(2 * P + 2, np.int64),
                "edge_ids": np.empty(nee, np.int64),
                "src_local": np.empty(nee, np.int64),
                "dst_local": np.empty(nee, np.int64),
            }
            if use_bond_graph:
                d.update(
                    bond_markers=np.empty(2 * P + 2, np.int64),
                    bond_global_edge=np.empty(nb, np.int64),
                    line_src=np.empty(nl, np.int64),
                    line_dst=np.empty(nl, np.int64),
                    line_center=np.empty(nl, np.int64),
                    bm_edge=np.empty(nm, np.int64),
                    bm_bond=np.empty(nm, np.int64),
                )
            args = [
                _ptr(d["global_ids"], ctypes.c_int64),
                _ptr(d["node_markers"], ctypes.c_int64),
                _ptr(d["edge_ids"], ctypes.c_int64),
                _ptr(d["src_local"], ctypes.c_int64),
                _ptr(d["dst_local"], ctypes.c_int64),
            ]
            if use_bond_graph:
                args += [
                    _ptr(d["bond_markers"], ctypes.c_int64),
                    _ptr(d["bond_global_edge"], ctypes.c_int64),
                    _ptr(d["line_src"], ctypes.c_int64),
                    _ptr(d["line_dst"], ctypes.c_int64),
                    _ptr(d["line_center"], ctypes.c_int64),
                    _ptr(d["bm_edge"], ctypes.c_int64),
                    _ptr(d["bm_bond"], ctypes.c_int64),
                ]
            else:
                args += [null] * 7
            lib.dm_partition_copy(h, p, *args)
            out.append(d)
        return out
    finally:
        lib.dm_partition_free(h)
