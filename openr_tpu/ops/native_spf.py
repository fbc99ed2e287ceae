"""ctypes bindings for the native SPF solver (native/spf/spf_solver.cpp).

reference: openr/decision/LinkState.cpp † runSpf. The native solver is a
radix-heap Dijkstra with ECMP first-hop bitmask propagation — the
latency-optimal shape for a SINGLE root on the host, complementing the
batched TPU fixpoint kernel (ops/spf_split.py) which owns multi-root /
all-sources shapes. `Decision` picks a backend per solve (config knob
`decision.spf_backend`), and the bench uses this as the in-run oracle.

The solver consumes a SOURCE-sorted CSR (out-edges); `CsrGraph` is
destination-sorted for the TPU relax, so `OutCsr.from_arrays` builds the
transposed view once per topology version and callers cache it keyed on
`csr.version`.
"""

from __future__ import annotations

import ctypes
from pathlib import Path

import numpy as np

from openr_tpu.common.constants import DIST_INF

_LIB_PATHS = (
    Path(__file__).resolve().parents[2] / "native" / "build"
    / "libopenr_spf.so",
)

_lib = None


def _load():
    global _lib
    if _lib is not None:
        return _lib
    for p in _LIB_PATHS:
        if p.exists():
            lib = ctypes.CDLL(str(p))
            break
    else:
        raise OSError(
            "libopenr_spf.so not built (run `make -C native`)"
        )
    i32p = ctypes.POINTER(ctypes.c_int32)
    i64p = ctypes.POINTER(ctypes.c_int64)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    u64p = ctypes.POINTER(ctypes.c_uint64)
    lib.openr_spf_dijkstra.argtypes = [
        ctypes.c_int32, i64p, i32p, i32p, u8p, ctypes.c_int32, i32p,
    ]
    lib.openr_spf_dijkstra.restype = ctypes.c_int
    lib.openr_spf_dijkstra_batch.argtypes = [
        ctypes.c_int32, i64p, i32p, i32p, u8p, i32p, ctypes.c_int32, i32p,
    ]
    lib.openr_spf_dijkstra_batch.restype = ctypes.c_int
    lib.openr_spf_rib.argtypes = [
        ctypes.c_int32, i64p, i32p, i32p, u8p, ctypes.c_int32,
        i32p, i32p, ctypes.c_int32, i32p, u64p,
    ]
    lib.openr_spf_rib.restype = ctypes.c_int
    _lib = lib
    return lib


def native_available() -> bool:
    try:
        _load()
        return True
    except OSError:
        return False


def _ptr(a: np.ndarray, ctype):
    return a.ctypes.data_as(ctypes.POINTER(ctype))


class OutCsr:
    """Source-sorted CSR out-edge view (row_start/dst/w) of the LSDB."""

    __slots__ = ("v", "row_start", "dst", "w", "overloaded")

    def __init__(self, v, row_start, dst, w, overloaded):
        self.v = v
        self.row_start = row_start
        self.dst = dst
        self.w = w
        self.overloaded = overloaded

    @classmethod
    def from_arrays(
        cls,
        edge_src: np.ndarray,
        edge_dst: np.ndarray,
        edge_metric: np.ndarray,
        num_nodes: int,
        node_overloaded: np.ndarray | None = None,
        return_slot_map: bool = False,
    ):
        """Build from the dst-sorted CsrGraph arrays. With
        `return_slot_map`, also return a [len(edge_src)] int64 map from
        original edge slot -> position in this CSR's w array (-1 for
        masked slots) so metric-only churn patches apply in O(1)."""
        valid = edge_metric < DIST_INF
        vi = np.nonzero(valid)[0]
        src = edge_src[valid].astype(np.int64)
        order = np.argsort(src, kind="stable")
        src = src[order]
        dst = np.ascontiguousarray(
            edge_dst[valid][order], dtype=np.int32
        )
        w = np.ascontiguousarray(edge_metric[valid][order], dtype=np.int32)
        row_start = np.zeros(num_nodes + 1, dtype=np.int64)
        np.add.at(row_start, src + 1, 1)
        row_start = np.cumsum(row_start)
        over = None
        if node_overloaded is not None and node_overloaded.any():
            over = np.ascontiguousarray(
                node_overloaded[:num_nodes], dtype=np.uint8
            )
        oc = cls(num_nodes, row_start, dst, w, over)
        if not return_slot_map:
            return oc
        slot_map = np.full(len(edge_src), -1, dtype=np.int64)
        slot_map[vi[order]] = np.arange(len(vi), dtype=np.int64)
        return oc, slot_map

    def _over_ptr(self):
        if self.overloaded is None:
            return ctypes.cast(None, ctypes.POINTER(ctypes.c_uint8))
        return _ptr(self.overloaded, ctypes.c_uint8)

    def dijkstra(self, root: int) -> np.ndarray:
        """Distances from `root`: [v] int32, DIST_INF = unreachable."""
        lib = _load()
        dist = np.empty(self.v, dtype=np.int32)
        rc = lib.openr_spf_dijkstra(
            self.v, _ptr(self.row_start, ctypes.c_int64),
            _ptr(self.dst, ctypes.c_int32), _ptr(self.w, ctypes.c_int32),
            self._over_ptr(), root, _ptr(dist, ctypes.c_int32),
        )
        if rc != 0:
            raise RuntimeError(f"openr_spf_dijkstra rc={rc}")
        return dist

    def dijkstra_batch(self, roots: np.ndarray) -> np.ndarray:
        """Distances from each root: [b, v] int32."""
        lib = _load()
        roots = np.ascontiguousarray(roots, dtype=np.int32)
        dist = np.empty((len(roots), self.v), dtype=np.int32)
        rc = lib.openr_spf_dijkstra_batch(
            self.v, _ptr(self.row_start, ctypes.c_int64),
            _ptr(self.dst, ctypes.c_int32), _ptr(self.w, ctypes.c_int32),
            self._over_ptr(), _ptr(roots, ctypes.c_int32), len(roots),
            _ptr(dist, ctypes.c_int32),
        )
        if rc != 0:
            raise RuntimeError(f"openr_spf_dijkstra_batch rc={rc}")
        return dist

    def rib_solve(
        self,
        root: int,
        nbr_ids: np.ndarray,
        nbr_metric: np.ndarray,
    ) -> tuple[np.ndarray, np.ndarray]:
        """(dist [v] i32, fh [n_nbrs, v] bool): distances from root and
        the ECMP first-hop validity matrix over the given neighbor slots
        (same layout as ops.spf.first_hop_matrix's output)."""
        lib = _load()
        n = len(nbr_ids)
        words = max(1, (n + 63) // 64)
        nbr_ids = np.ascontiguousarray(nbr_ids, dtype=np.int32)
        nbr_metric = np.ascontiguousarray(nbr_metric, dtype=np.int32)
        dist = np.empty(self.v, dtype=np.int32)
        fh_bits = np.zeros((self.v, words), dtype=np.uint64)
        rc = lib.openr_spf_rib(
            self.v, _ptr(self.row_start, ctypes.c_int64),
            _ptr(self.dst, ctypes.c_int32), _ptr(self.w, ctypes.c_int32),
            self._over_ptr(), root,
            _ptr(nbr_ids, ctypes.c_int32), _ptr(nbr_metric, ctypes.c_int32),
            n, _ptr(dist, ctypes.c_int32),
            _ptr(fh_bits, ctypes.c_uint64),
        )
        if rc != 0:
            raise RuntimeError(f"openr_spf_rib rc={rc}")
        if n == 0:
            return dist, np.zeros((0, self.v), dtype=bool)
        # unpack bitmask words -> [n, v] bool
        slots = np.arange(n)
        word_of = slots >> 6
        bit_of = np.uint64(1) << (slots & 63).astype(np.uint64)
        fh = (fh_bits[:, word_of] & bit_of[None, :]) != 0  # [v, n]
        return dist, np.ascontiguousarray(fh.T)
