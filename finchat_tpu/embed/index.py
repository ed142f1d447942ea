"""On-device vector index.

The reference searches a remote Qdrant over HNSW (``tools/qdrant_tool.py``).
The TPU-native default is exact brute-force cosine on the MXU: one
``scores = V @ Q^T`` matmul over the whole collection per dispatch — for the
collection sizes this product sees (per-user bank transactions), exact
search on-device beats a network round-trip to an approximate index, and
security filtering stays in-process.

Two query planes, golden-equivalent (tests/test_retrieval_plane.py):

- ``query_points`` — the serial host-mask path: the boolean filter mask is
  built in numpy per query, then one ``V @ q`` scoring dispatch. Kept as
  the reference implementation and fallback.
- ``query_points_batch`` — the batched device-filter path the retrieval
  plane uses: B queries score in ONE ``V @ Q^T`` dispatch, and the
  must-filters (user_id equality, date >= bound) evaluate ON DEVICE
  against int-coded filter columns (interned user codes + dates) that
  live device-resident and are maintained incrementally on upsert — no
  per-query host mask rebuild, no whole-matrix re-upload when new rows
  land (``dynamic_update_slice`` splices just the new rows).

Data model parity (SURVEY §2.4): points carry payload
``{page_content: str, metadata: {user_id, date: unix-ts, ...}}``; filters
are ``must user_id == X`` plus optional ``metadata.date >= now - N days``
(qdrant_tool.py:105-126).
"""

from __future__ import annotations

import json
import os
import threading
from dataclasses import dataclass, field
from functools import partial
from pathlib import Path
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from finchat_tpu.utils.logging import get_logger

logger = get_logger(__name__)

# user-code sentinels for the device-side filter: NO_FILTER matches every
# row; NO_MATCH (an unknown user_id — no row can carry it) matches none
NO_FILTER_CODE = -1
NO_MATCH_CODE = -2


@dataclass
class VectorPoint:
    id: str
    vector: np.ndarray  # [dim] fp32 (normalized or not; scoring normalizes)
    payload: dict[str, Any] = field(default_factory=dict)

    @property
    def metadata(self) -> dict[str, Any]:
        return self.payload.get("metadata", {}) or {}


@dataclass(frozen=True)
class QuerySpec:
    """One query of a batched ``query_points_batch`` call."""

    vector: np.ndarray
    limit: int
    user_id: str | None = None
    date_gte: float | None = None


@partial(jax.jit, static_argnames=("k",))
def _topk_scores(vectors: jnp.ndarray, mask: jnp.ndarray, query: jnp.ndarray, *, k: int):
    """scores = V·q with invalid rows masked to -inf; returns (scores, idx)."""
    scores = vectors @ query  # [N] — the MXU does the work
    scores = jnp.where(mask, scores, -jnp.inf)
    return jax.lax.top_k(scores, k)


def _split_f64(x: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Double-single split: float64 → (hi, lo) float32 pair with
    ``hi + lo == x`` to ~48-bit precision. Unix timestamps (~2^31 s) are
    far beyond float32's 24-bit mantissa (128 s spacing at current
    epoch), so a single-f32 date column would mis-filter rows within
    ~2 min of the cutoff where the serial float64 host path classifies
    them exactly; the lexicographic (hi, lo) compare below keeps the
    batched plane golden-equivalent down to sub-millisecond date
    resolution."""
    x = np.asarray(x, np.float64)
    hi = x.astype(np.float32)
    # -inf encodes "no date filter": its hi alone decides every compare,
    # so pin lo to 0 there (inf - inf would be NaN)
    finite = np.isfinite(x)
    lo = np.zeros_like(x)
    np.subtract(x, hi.astype(np.float64), out=lo, where=finite)
    return hi, lo.astype(np.float32)


@partial(jax.jit, static_argnames=("k",))
def _topk_scores_batch(
    vectors: jnp.ndarray,      # [N, dim] fp32
    alive: jnp.ndarray,        # [N] bool
    user_codes: jnp.ndarray,   # [N] int32 (interned user ids)
    dates_hi: jnp.ndarray,     # [N] fp32 unix ts (double-single hi)
    dates_lo: jnp.ndarray,     # [N] fp32 unix ts (double-single lo)
    q: jnp.ndarray,            # [B, dim] fp32 (rows L2-normalized)
    q_codes: jnp.ndarray,      # [B] int32 (NO_FILTER_CODE = no user filter)
    q_date_hi: jnp.ndarray,    # [B] fp32 (-inf = no date filter)
    q_date_lo: jnp.ndarray,    # [B] fp32
    *,
    k: int,
):
    """B queries in one dispatch: scores = V @ Q^T with the must-filter
    masks built ON DEVICE from the resident filter columns (no host-side
    mask rebuild per query); returns ([B, k] scores, [B, k] idx)."""
    scores = (vectors @ q.T).T  # [B, N]
    user_ok = (q_codes[:, None] == NO_FILTER_CODE) | (
        user_codes[None, :] == q_codes[:, None]
    )
    # date >= cutoff, exact over the double-single pairs: lexicographic on
    # (hi, lo) — valid because both sides come from the same split
    hi_n, lo_n = dates_hi[None, :], dates_lo[None, :]
    hi_q, lo_q = q_date_hi[:, None], q_date_lo[:, None]
    date_ok = (hi_n > hi_q) | ((hi_n == hi_q) & (lo_n >= lo_q))
    mask = alive[None, :] & user_ok & date_ok  # [B, N]
    scores = jnp.where(mask, scores, -jnp.inf)
    return jax.lax.top_k(scores, k)


@partial(jax.jit, donate_argnums=(0,))
def _splice_rows(dst: jnp.ndarray, rows: jnp.ndarray, start: jnp.ndarray):
    """Incremental device upload: write ``rows`` into ``dst`` at row
    ``start`` in place (donated) — upserting M new rows moves M·dim
    floats host→device instead of re-uploading the whole matrix."""
    return jax.lax.dynamic_update_slice(dst, rows, (start,) + (0,) * (dst.ndim - 1))


class DeviceVectorIndex:
    """Append-mostly vector store with device-side scoring.

    Host keeps payloads + filter columns (user_id, date) as numpy; the
    device keeps a padded, L2-normalized matrix [capacity, dim] plus the
    int-coded filter columns. Capacity doubles on overflow (full
    re-upload); within a capacity, new rows splice in incrementally.
    Deletes are tombstones.
    """

    def __init__(self, dim: int, initial_capacity: int = 1024):
        self.dim = dim
        self._lock = threading.Lock()
        # serializes whole snapshots against each other (two concurrent
        # save() calls would race on the same .tmp paths) without making
        # queries wait on compression/file IO
        self._save_lock = threading.Lock()
        self._capacity = initial_capacity
        self._count = 0
        self._points: list[VectorPoint] = []
        self._user_ids: list[str] = []
        # interned user codes: one int per distinct user_id, maintained
        # incrementally on upsert so no query path ever rebuilds an array
        # from the Python string list
        self._user_interner: dict[str, int] = {}
        self._user_codes: np.ndarray = np.full((initial_capacity,), NO_MATCH_CODE, np.int32)
        self._dates: np.ndarray = np.zeros((initial_capacity,), np.float64)
        self._alive: np.ndarray = np.zeros((initial_capacity,), bool)
        self._host_vectors = np.zeros((initial_capacity, dim), np.float32)
        self._device_vectors = jnp.zeros((initial_capacity, dim), jnp.float32)
        self._device_alive = jnp.zeros((initial_capacity,), bool)
        self._device_user_codes = jnp.full((initial_capacity,), NO_MATCH_CODE, jnp.int32)
        # dates as a double-single (hi, lo) float32 pair — see _split_f64
        self._device_dates_hi = jnp.zeros((initial_capacity,), jnp.float32)
        self._device_dates_lo = jnp.zeros((initial_capacity,), jnp.float32)
        self._synced_rows = 0   # device rows that mirror the host arrays
        self._full_dirty = False  # growth / external mutation: re-upload all

    def __len__(self) -> int:
        return sum(self._alive[: self._count])

    @staticmethod
    def _normalize(v: np.ndarray) -> np.ndarray:
        norm = np.linalg.norm(v, axis=-1, keepdims=True)
        return v / np.maximum(norm, 1e-9)

    def _intern(self, user_id: str) -> int:
        code = self._user_interner.get(user_id)
        if code is None:
            code = len(self._user_interner)
            self._user_interner[user_id] = code
        return code

    def _grow(self, needed: int) -> None:
        new_cap = self._capacity
        while new_cap < needed:
            new_cap *= 2
        pad = new_cap - self._capacity
        self._host_vectors = np.concatenate([self._host_vectors, np.zeros((pad, self.dim), np.float32)])
        self._dates = np.concatenate([self._dates, np.zeros((pad,), np.float64)])
        self._alive = np.concatenate([self._alive, np.zeros((pad,), bool)])
        self._user_codes = np.concatenate(
            [self._user_codes, np.full((pad,), NO_MATCH_CODE, np.int32)]
        )
        self._capacity = new_cap
        self._full_dirty = True  # device arrays must be rebuilt at new shape

    def upsert(self, points: list[VectorPoint]) -> None:
        with self._lock:
            if self._count + len(points) > self._capacity:
                self._grow(self._count + len(points))
            for p in points:
                row = self._count
                self._host_vectors[row] = self._normalize(np.asarray(p.vector, np.float32))
                self._dates[row] = float(p.metadata.get("date", 0) or 0)
                self._alive[row] = True
                self._points.append(p)
                uid = str(p.metadata.get("user_id", ""))
                self._user_ids.append(uid)
                self._user_codes[row] = self._intern(uid)
                self._count += 1

    def _sync_device(self) -> None:
        """Bring the device arrays up to date with the host arrays. Full
        re-upload only on growth/external mutation; the steady-state ingest
        path splices just the rows added since the last sync."""
        if self._full_dirty:
            hi, lo = _split_f64(self._dates)
            self._device_vectors = jnp.asarray(self._host_vectors)
            self._device_alive = jnp.asarray(self._alive)
            self._device_user_codes = jnp.asarray(self._user_codes)
            self._device_dates_hi = jnp.asarray(hi)
            self._device_dates_lo = jnp.asarray(lo)
            self._synced_rows = self._count
            self._full_dirty = False
            return
        lo, hi = self._synced_rows, self._count
        if lo >= hi:
            return
        # pad the splice to a power-of-two row count (clamped to capacity)
        # so streaming ingest compiles at most log2(capacity) splice
        # variants; the padding rows carry host truth, so overwriting them
        # is idempotent
        padded_hi = min(lo + self._query_bucket(hi - lo), self._capacity)
        start = jnp.int32(lo)
        self._device_vectors = _splice_rows(
            self._device_vectors, jnp.asarray(self._host_vectors[lo:padded_hi]), start
        )
        self._device_alive = _splice_rows(
            self._device_alive, jnp.asarray(self._alive[lo:padded_hi]), start
        )
        self._device_user_codes = _splice_rows(
            self._device_user_codes, jnp.asarray(self._user_codes[lo:padded_hi]), start
        )
        d_hi, d_lo = _split_f64(self._dates[lo:padded_hi])
        self._device_dates_hi = _splice_rows(self._device_dates_hi, jnp.asarray(d_hi), start)
        self._device_dates_lo = _splice_rows(self._device_dates_lo, jnp.asarray(d_lo), start)
        self._synced_rows = hi

    def query_points(
        self,
        query_vector: np.ndarray,
        *,
        limit: int,
        user_id: str | None = None,
        date_gte: float | None = None,
    ) -> list[VectorPoint]:
        """Top-``limit`` cosine matches under the must-filters, best first.

        Serial host-mask path: the filter mask builds in numpy (from the
        incrementally-maintained code column, not the Python list), then
        one single-query scoring dispatch. The batched device-filter plane
        (``query_points_batch``) must stay golden-equivalent to this."""
        with self._lock:
            if self._count == 0:
                return []
            self._sync_device()
            mask = self._alive[: self._capacity].copy()
            mask[self._count :] = False
            if user_id is not None:
                code = self._user_interner.get(user_id, NO_MATCH_CODE)
                mask[: self._count] &= self._user_codes[: self._count] == code
            if date_gte is not None:
                mask[: self._count] &= self._dates[: self._count] >= date_gte
            if not mask.any():
                return []
            q = self._normalize(np.asarray(query_vector, np.float32))
            # k is static in the compiled top-k: a power of two, cut to the
            # limit on the host (best first, so the cut is the top-limit), or
            # every limit a model asks for compiles a program of its own
            k = min(self._query_bucket(limit), self._capacity)
            scores, idx = _topk_scores(self._device_vectors, jnp.asarray(mask), jnp.asarray(q), k=k)
            scores = np.asarray(scores)[:limit]
            idx = np.asarray(idx)[:limit]
            out: list[VectorPoint] = []
            for s, i in zip(scores, idx):
                if not np.isfinite(s):
                    break
                out.append(self._points[int(i)])
            return out

    @staticmethod
    def _query_bucket(n: int) -> int:
        b = 1
        while b < n:
            b *= 2
        return b

    def query_points_batch(self, queries: list[QuerySpec]) -> list[list[VectorPoint]]:
        """Top-k for B queries in ONE device dispatch (``V @ Q^T`` scoring,
        on-device must-filter masks). The query batch pads to a power of
        two so concurrent fan-in compiles at most log2 variants per
        (capacity, k) pair. Returns one best-first hit list per query,
        golden-equivalent to ``query_points`` run serially."""
        if not queries:
            return []
        with self._lock:
            if self._count == 0:
                return [[] for _ in queries]
            self._sync_device()
            B = self._query_bucket(len(queries))
            q = np.zeros((B, self.dim), np.float32)
            q_codes = np.full((B,), NO_MATCH_CODE, np.int32)  # padding matches nothing
            q_dates = np.full((B,), -np.inf, np.float64)
            limits = []
            for i, spec in enumerate(queries):
                q[i] = self._normalize(np.asarray(spec.vector, np.float32))
                if spec.user_id is None:
                    q_codes[i] = NO_FILTER_CODE
                else:
                    q_codes[i] = self._user_interner.get(spec.user_id, NO_MATCH_CODE)
                if spec.date_gte is not None:
                    q_dates[i] = spec.date_gte
                limits.append(min(int(spec.limit), self._capacity))
            # a power of two, as in query_points: each row is cut to its
            # own limit below
            k = min(self._query_bucket(max(limits)), self._capacity)
            q_hi, q_lo = _split_f64(q_dates)
            scores, idx = _topk_scores_batch(
                self._device_vectors, self._device_alive,
                self._device_user_codes, self._device_dates_hi, self._device_dates_lo,
                jnp.asarray(q), jnp.asarray(q_codes),
                jnp.asarray(q_hi), jnp.asarray(q_lo),
                k=k,
            )
            scores = np.asarray(scores)
            idx = np.asarray(idx)
            results: list[list[VectorPoint]] = []
            for i in range(len(queries)):
                out: list[VectorPoint] = []
                for s, j in zip(scores[i, : limits[i]], idx[i, : limits[i]]):
                    if not np.isfinite(s):
                        break
                    out.append(self._points[int(j)])
                results.append(out)
            return results

    # --- durability (VERDICT r1 task 5) ---------------------------------
    # The reference's collection lives in an external, durable Qdrant
    # (qdrant_tool.py:24-37); the on-device index persists to a local
    # snapshot instead so retrieval is not empty-at-boot.

    def save(self, path: str) -> None:
        """Atomic snapshot: vectors as .npz, payloads as .jsonl sidecar.

        ``_lock`` is held only long enough to COPY the arrays and payload
        refs — compression and file IO run outside it, so a snapshot never
        stalls concurrent queries/upserts for the write's duration.
        ``_save_lock`` serializes overlapping save() calls (debounced
        ingest persist racing a forced shutdown persist), which would
        otherwise interleave writes to the same .tmp files."""
        with self._save_lock:
            with self._lock:
                n = self._count
                vectors = self._host_vectors[:n].copy()
                dates = self._dates[:n].copy()
                alive = self._alive[:n].copy()
                points = list(self._points)
            base = Path(path)
            base.parent.mkdir(parents=True, exist_ok=True)
            # np.savez appends ".npz" unless the name already ends with it
            tmp_vec = str(base) + ".tmp.npz"
            np.savez_compressed(tmp_vec, vectors=vectors, dates=dates, alive=alive)
            tmp_pay = str(base) + ".jsonl.tmp"
            with open(tmp_pay, "w") as f:
                for p in points:
                    f.write(json.dumps({"id": p.id, "payload": p.payload}) + "\n")
            os.replace(tmp_vec, str(base) + ".npz")
            os.replace(tmp_pay, str(base) + ".jsonl")
        logger.info("vector index saved: %d points -> %s.{npz,jsonl}", n, path)

    @classmethod
    def load(cls, path: str, dim: int) -> "DeviceVectorIndex":
        """Restore a snapshot; a missing snapshot yields an empty index."""
        base = Path(path)
        vec_file, pay_file = Path(str(base) + ".npz"), Path(str(base) + ".jsonl")
        index = cls(dim=dim)
        if not (vec_file.exists() and pay_file.exists()):
            logger.info("no vector snapshot at %s; starting empty", path)
            return index
        data = np.load(vec_file)
        vectors, dates, alive = data["vectors"], data["dates"], data["alive"]
        with open(pay_file) as f:  # finchat-lint: disable=event-loop-blocking -- startup snapshot load (build_app runs it before the loop serves); ingest-path saves already copy-then-write off-lock
            records = [json.loads(line) for line in f]
        if len(records) != len(vectors):
            # a crash between the two os.replace calls in save() can tear
            # the snapshot; fail with a clear message, not an IndexError
            raise ValueError(
                f"snapshot mismatch at {path}: {len(vectors)} vectors vs "
                f"{len(records)} payloads (torn snapshot?)"
            )
        points = [
            VectorPoint(id=rec["id"], vector=vectors[row], payload=rec["payload"])
            for row, rec in enumerate(records)
        ]
        index.upsert(points)
        # restore tombstones + original dates exactly; the device mirrors
        # are stale after this direct mutation — force a full re-upload
        index._alive[: len(points)] = alive
        index._dates[: len(points)] = dates
        index._full_dirty = True
        logger.info("vector index restored: %d points from %s", len(points), path)
        return index
