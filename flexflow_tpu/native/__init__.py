"""ctypes loader for the native search engine (native/ffsim.cc).

The reference's search/simulator layer is C++ (src/runtime/simulator.cc,
model.cc mcmc); ours is too — Python prices (node, view) pairs with the
analytic TPU cost model, and libffsim owns the hot loops. The library is
built on demand with g++ (no pybind11 in this image; plain C ABI +
ctypes). Everything degrades gracefully to the pure-Python path when no
compiler is available: callers must check `available()`.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
from typing import Optional

_PKG_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_PKG_DIR, "..", "..", "native", "ffsim.cc")
_LIB_PATH = os.path.join(_PKG_DIR, "libffsim.so")

_lib: Optional[ctypes.CDLL] = None
_tried = False


def _src_hash(src: str) -> str:
    with open(src, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def _build_so(src: str, lib_path: str, extra_flags=()) -> bool:
    """Compile `src` to `lib_path` unless the library on disk was built
    from exactly this source: the sha256 of the source it came from sits
    beside it in `<lib>.src-sha256` (native/Makefile writes the same
    sidecar). File times say nothing — a copy or a checkout scrambles
    them — so a library without a matching sidecar is rebuilt, never
    loaded. Atomic tmp+replace, so a concurrent process never dlopens a
    partially written .so."""
    src = os.path.abspath(src)
    if not os.path.exists(src):
        return False
    want = _src_hash(src)
    stamp = lib_path + ".src-sha256"
    try:
        with open(stamp) as f:
            if os.path.exists(lib_path) and f.read().strip() == want:
                return True
    except OSError:
        pass
    tmp = f"{lib_path}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["g++", "-O2", "-std=c++17", "-fPIC", "-shared", *extra_flags,
             "-o", tmp, src],
            check=True, capture_output=True, timeout=120,
        )
        os.replace(tmp, lib_path)
        with open(tmp, "w") as f:
            f.write(want + "\n")
        os.replace(tmp, stamp)
        return True
    except (OSError, subprocess.SubprocessError):
        return False
    finally:
        if os.path.exists(tmp):
            try:
                os.remove(tmp)
            except OSError:
                pass


def _build() -> bool:
    return _build_so(_SRC, _LIB_PATH)


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    dp = ctypes.POINTER(ctypes.c_double)
    ip = ctypes.POINTER(ctypes.c_int)
    lib.ffsim_create.restype = ctypes.c_void_p
    lib.ffsim_create.argtypes = [ctypes.c_int]
    lib.ffsim_destroy.argtypes = [ctypes.c_void_p]
    lib.ffsim_set_node.argtypes = [ctypes.c_void_p, ctypes.c_int, ctypes.c_int,
                                   dp, dp, dp, dp]
    lib.ffsim_add_edge.argtypes = [ctypes.c_void_p, ctypes.c_int, ctypes.c_int, dp]
    lib.ffsim_eval.restype = ctypes.c_double
    lib.ffsim_eval.argtypes = [ctypes.c_void_p, ip, ctypes.c_double, dp]
    lib.ffsim_simulate.restype = ctypes.c_double
    lib.ffsim_simulate.argtypes = [ctypes.c_void_p, ip]
    lib.ffsim_mcmc.restype = ctypes.c_int
    lib.ffsim_mcmc.argtypes = [ctypes.c_void_p, ctypes.c_int, ctypes.c_double,
                               ctypes.c_uint64, ctypes.c_double, ctypes.c_double,
                               ctypes.c_int, ip, dp]
    lib.ffsim_tasksim_build.restype = ctypes.c_void_p
    lib.ffsim_tasksim_build.argtypes = [ctypes.c_int, ctypes.c_int, ip, dp,
                                        ctypes.c_int, ip, ip]
    lib.ffsim_tasksim_destroy.argtypes = [ctypes.c_void_p]
    lib.ffsim_tasksim_run.restype = ctypes.c_double
    lib.ffsim_tasksim_run.argtypes = [ctypes.c_void_p]
    return lib


def get_lib() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    _tried = True
    if os.environ.get("FLEXFLOW_NATIVE", "1") == "0":
        return None
    if _build():
        try:
            _lib = _bind(ctypes.CDLL(_LIB_PATH))
        except OSError:
            _lib = None
    return _lib


def available() -> bool:
    return get_lib() is not None


def engine() -> str:
    """Which search engine this process runs, for entry points that
    must say so (chip_smoke.py): the C++ one, or the pure-Python
    fallback and why."""
    if available():
        return "native (libffsim.so, built from native/ffsim.cc)"
    if os.environ.get("FLEXFLOW_NATIVE", "1") == "0":
        return "python (FLEXFLOW_NATIVE=0)"
    return "python (libffsim.so could not be built or loaded: no g++?)"


class NativeSimGraph:
    """Owns one ffsim graph handle; rows are (node, view) cost tables."""

    def __init__(self, n_nodes: int):
        self._lib = get_lib()
        if self._lib is None:
            raise RuntimeError("native ffsim library unavailable")
        self._h = self._lib.ffsim_create(n_nodes)
        self.n_nodes = n_nodes
        self._n_views = [0] * n_nodes

    def __del__(self):
        if getattr(self, "_h", None) and self._lib is not None:
            self._lib.ffsim_destroy(self._h)
            self._h = None

    @staticmethod
    def _darr(vals):
        return (ctypes.c_double * len(vals))(*vals)

    def set_node(self, node, compute, comm, sync, memory):
        n = len(compute)
        assert len(comm) == len(sync) == len(memory) == n
        self._n_views[node] = n
        self._lib.ffsim_set_node(
            self._h, node, n, self._darr(compute), self._darr(comm),
            self._darr(sync), self._darr(memory),
        )

    def add_edge(self, src, dst, xfer_matrix):
        flat = [x for row in xfer_matrix for x in row]
        assert len(flat) == self._n_views[src] * self._n_views[dst]
        self._lib.ffsim_add_edge(self._h, src, dst, self._darr(flat))

    def _iarr(self, assignment):
        assert len(assignment) == self.n_nodes
        return (ctypes.c_int * self.n_nodes)(*assignment)

    def eval(self, assignment, overlap: float = 0.0):
        mem = ctypes.c_double()
        t = self._lib.ffsim_eval(self._h, self._iarr(assignment), overlap,
                                 ctypes.byref(mem))
        return t, mem.value

    def simulate(self, assignment) -> float:
        return self._lib.ffsim_simulate(self._h, self._iarr(assignment))

    def mcmc(self, assignment, *, budget: int, alpha: float, seed: int = 0,
             overlap: float = 0.0, memory_limit: float = 0.0,
             use_simulate: bool = False):
        arr = self._iarr(assignment)
        best_cost = ctypes.c_double()
        accepted = self._lib.ffsim_mcmc(
            self._h, budget, alpha, seed, overlap, memory_limit,
            1 if use_simulate else 0, arr, ctypes.byref(best_cost),
        )
        return list(arr), best_cost.value, accepted


def run_task_dag(n_channels: int, channels, durations, dep_src, dep_dst):
    """List-schedule a task DAG on `n_channels` serial channels (per-chip
    compute + per-axis ICI — see native/ffsim.cc ffsim_tasksim_build) and
    return the makespan, or None when the native engine is unavailable.
    `channels`/`durations`/`dep_*` are flat sequences (numpy arrays fine);
    the whole DAG ships in one call to keep ctypes off the hot loop."""
    lib = get_lib()
    if lib is None:
        return None
    import numpy as np

    # one bulk conversion per array — per-element ctypes marshalling would
    # dominate the C scheduler on the search hot path
    ch = np.ascontiguousarray(channels, dtype=np.int32)
    du = np.ascontiguousarray(durations, dtype=np.float64)
    ds = np.ascontiguousarray(dep_src, dtype=np.int32)
    dd = np.ascontiguousarray(dep_dst, dtype=np.int32)
    ip = ctypes.POINTER(ctypes.c_int)
    dp = ctypes.POINTER(ctypes.c_double)
    h = lib.ffsim_tasksim_build(
        n_channels, len(ch), ch.ctypes.data_as(ip), du.ctypes.data_as(dp),
        len(ds), ds.ctypes.data_as(ip), dd.ctypes.data_as(ip))
    try:
        t = lib.ffsim_tasksim_run(h)
    finally:
        lib.ffsim_tasksim_destroy(h)
    return None if t < 0 else t


# ---------------------------------------------------------------------------
# native data loader (native/ffloader.cc — flexflow_dataloader.cc analog)

_LOADER_SRC = os.path.join(_PKG_DIR, "..", "..", "native", "ffloader.cc")
_LOADER_LIB_PATH = os.path.join(_PKG_DIR, "libffloader.so")
_loader_lib: Optional[ctypes.CDLL] = None
_loader_tried = False


def _build_loader() -> bool:
    return _build_so(_LOADER_SRC, _LOADER_LIB_PATH, extra_flags=("-pthread",))


def get_loader_lib() -> Optional[ctypes.CDLL]:
    global _loader_lib, _loader_tried
    if _loader_lib is not None or _loader_tried:
        return _loader_lib
    _loader_tried = True
    if os.environ.get("FLEXFLOW_NATIVE", "1") == "0":
        return None
    if _build_loader():
        try:
            lib = ctypes.CDLL(_LOADER_LIB_PATH)
            lib.ffl_open.restype = ctypes.c_void_p
            lib.ffl_open.argtypes = [ctypes.c_char_p, ctypes.c_long,
                                     ctypes.c_long, ctypes.c_long]
            lib.ffl_config.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                       ctypes.c_int, ctypes.c_long]
            lib.ffl_reset.argtypes = [ctypes.c_void_p]
            lib.ffl_next.restype = ctypes.c_int
            lib.ffl_next.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                     ctypes.c_long]
            lib.ffl_close.argtypes = [ctypes.c_void_p]
            _loader_lib = lib
        except OSError:
            _loader_lib = None
    return _loader_lib


def loader_available() -> bool:
    return get_loader_lib() is not None
