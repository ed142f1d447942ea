"""Session KV cache: a byte-budgeted host-RAM tier for cross-turn prefix resume.

The reference is a multi-turn chatbot whose every Kafka message re-fetches the
whole conversation history and re-prefills it from token zero
(serve/app.py process_message), so turn-N TTFT grows linearly with history
even though the engine computed that exact KV last turn. The shared-prefix
entries (scheduler ``_PrefixEntry``) only cover the constant system-prompt
head shared by ALL conversations; this module adds the per-conversation tier
below it — the hierarchical KV management that serving stacks built on paged
attention standardize on (Ragged Paged Attention, arXiv:2604.15464; long-
sequence state streaming, SnapStream, arXiv:2511.03092):

- OFFLOAD: when a sequence retires normally (eos/length), the scheduler
  snapshots its KV pages device→host (``InferenceEngine.offload_pages``)
  BEFORE the pages are freed, keyed by ``conversation_id``.
- RESUME: when the conversation's next turn arrives, admission matches the
  new prompt against the stored token stream — longest common token prefix,
  floored to page granularity — allocates fresh device pages, copies the
  matched pages host→device (``InferenceEngine.restore_pages``), and starts
  prefill at the matched offset.
- DIVERGENCE TRUNCATION: a turn whose history was edited (or re-rendered
  differently) matches only up to the divergence point; the entry is
  truncated there so stale KV can never be served.
- COMPOSITION with the shared-prefix cache: an entry whose sequence rode a
  refcounted ``_PrefixEntry`` head records those device pages BY REFERENCE
  (holding a ref so retirement cannot free them) and snapshots only the
  sequence's OWN pages — the constant head is never copied to host and
  never duplicated on restore.
- LRU under a byte budget: host bytes are the sum of the entries' own-page
  snapshots; inserting past ``budget_bytes`` evicts least-recently-used
  conversations first.
- DISK TIER (ISSUE 7; ROBUSTNESS.md §5): with ``engine.session_cache_disk_
  path`` set, every stored entry is also written through to a checksummed,
  versioned record file (atomic write-rename), the disk tier keeps its own
  byte-budgeted LRU over those records, and a RAM miss at admission falls
  through to disk (scheduler ``_restore_session_from_disk``). Because the
  records are write-through — not written only at eviction — a full
  process kill loses at most the turn that was mid-stream: the restarted
  process sweeps the directory, rebuilds the index, and the next turn of
  any retired conversation resumes warm. A corrupt or truncated record is
  QUARANTINED (renamed aside, counted) and the conversation cold-starts;
  stale or diverged records are harmless because every restore re-enters
  ``match``'s token comparison and divergence truncation.

Ownership contract (the allocator invariants of SURVEY §5.2 are untouched):
the cache NEVER owns device pages. Snapshots are host copies taken while the
retiring sequence still owns its pages; restores write into pages freshly
allocated to (and owned by) the admitted sequence. The only device pages an
entry points at are the shared-prefix head's, which stay owned by their
``__prefix_*__`` owner and are protected by the entry's reference count.

Everything here runs on the scheduler's host path (admission / retirement),
never inside a jitted step — the D2H/H2D copies are per-turn costs, not
per-token ones.
"""

from __future__ import annotations

import hashlib
import json
import os
import threading
import zlib
from collections import OrderedDict
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable

import numpy as np

from finchat_tpu.utils.faults import inject
from finchat_tpu.utils.logging import get_logger
from finchat_tpu.utils.metrics import METRICS
from finchat_tpu.utils.tracing import TRACER

logger = get_logger(__name__)

# Cache-key convention, shared across layers: the agent keys each LLM
# role's entry separately (the two roles render DIFFERENT prompts for one
# conversation, so a shared key would cross-truncate every turn), and the
# fleet router must map any such key back to the conversation it belongs
# to — routing and migration are per-CONVERSATION, entries are per-ROLE.
SESSION_KEY_ROLES = ("tool", "resp")


def session_key(conversation_id: str, role: str) -> str:
    """The session-cache key for one LLM role of a conversation."""
    return f"{conversation_id}#{role}"


def conversation_of(key: str) -> str:
    """Inverse of :func:`session_key` for routing: the conversation a
    cache key (or a handle's ``conversation_id``) belongs to. Keys without
    a recognised role suffix — direct scheduler submissions —
    are their own conversation."""
    base, sep, role = key.rpartition("#")
    return base if sep and role in SESSION_KEY_ROLES else key


# Snapshot layout throughout this module: a (k, v, k_scales | None,
# v_scales | None) tuple of host arrays, each [L, n_pages, ...] — the
# gather_pages_host / scatter_pages_device contract (engine/kv_cache.py).
# Under ``kv_quant="int8"`` the data planes are int8 and the scale planes
# are REAL fp32 arrays — both travel through every snapshot path (RAM LRU,
# disk records, fleet export) byte-identically; scales are covered by the
# record CRC like everything else in the payload.


def snap_kv_mode(snap: tuple | None) -> str:
    """The KV quant mode a snapshot was taken under: "int8" when it
    carries scale planes, "" (native dtype) otherwise. ``None`` snapshots
    (prefix-only entries) are mode-agnostic — restorable under either."""
    if snap is None or len(snap) < 3 or snap[2] is None:
        return ""
    return "int8"


def _dtype_name(dt) -> str:
    """Serializable dtype identity. ``np.dtype.str`` is NOT it: ml_dtypes
    dtypes (bfloat16) stringify as ``<V2`` (raw void), which round-trips
    to a void dtype — a bf16 snapshot written that way can never restore
    (latent since ISSUE 7; record version 2 fixes it). ``.name`` gives
    'bfloat16'/'float32'/'int8', resolvable by :func:`resolve_dtype`."""
    return np.dtype(dt).name


def resolve_dtype(name: str) -> np.dtype:
    """Inverse of :func:`_dtype_name`, also accepting v1 records' dtype
    strings ('<f4' etc.). Unknown names raise — the caller quarantines."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


def snap_nbytes(snap: tuple | None) -> int:
    if snap is None:
        return 0
    return sum(int(a.nbytes) for a in snap if a is not None)


def concat_snaps(head: tuple | None, n_head_pages: int, tail: tuple | None) -> tuple | None:
    """The first ``n_head_pages`` pages of ``head`` followed by all of
    ``tail`` — the incremental-offload splice: a retiring turn reuses the
    previous entry's host bytes for pages it restored (and never rewrote)
    and only the pages written this turn arrive as a fresh D2H ``tail``.
    Always copies, so the result never aliases the (soon-dropped) head."""
    if n_head_pages == 0 or head is None:
        return tail
    sliced = tuple(a[:, :n_head_pages] if a is not None else None for a in head)
    if tail is None:
        return tuple(
            np.ascontiguousarray(a) if a is not None else None for a in sliced
        )
    return tuple(
        np.concatenate([a, b], axis=1) if a is not None else None
        for a, b in zip(sliced, tail)
    )


def _slice_snap(snap: tuple | None, n_pages: int) -> tuple | None:
    """First ``n_pages`` pages of a snapshot, compacted so truncation
    actually releases host RAM (a view would pin the full buffer)."""
    if snap is None or n_pages == 0:
        return None
    return tuple(
        np.ascontiguousarray(a[:, :n_pages]) if a is not None else None
        for a in snap
    )


class SessionDiskTier:
    """Byte-budgeted LRU of session-KV record files under one directory —
    the durability plane below the host-RAM tier (ISSUE 7).

    Record format (version 2; version 1 records remain readable):

        b"FSKV" | u8 version | u32 header_len | header JSON | payload

    The header carries the cache key, ``prefix_len``, the array specs
    (dtype/shape per array; the shared-prefix head's DEVICE pages are
    never stored — the record is the ``export_entry`` payload shape, so
    a restore re-links against the restoring scheduler's own live head),
    the payload byte length, and a CRC32 of the payload. Version 2
    (ISSUE 14) additionally stamps the snapshot's KV quant mode (``kv``:
    "int8" when scale planes travel, "" for native dtype — the scale
    planes ride the payload and its CRC like every other array) and
    stores dtypes BY NAME: v1 used ``np.dtype.str``, under which
    ml_dtypes bfloat16 serializes as raw void (``<V2``) and can never
    deserialize — v1 bf16 records were unreadable (quarantine → cold
    start); v2 round-trips every serving dtype. Writes go to a ``.tmp``
    sibling, fsync, then ``os.replace`` — a record is either whole or
    absent, never torn. Any read-side anomaly (bad magic, version,
    truncation, CRC mismatch, or an injected ``disk.restore`` fault)
    QUARANTINES the file (renamed ``*.quarantine``) and returns None:
    never a crash, never stale KV — the conversation cold-starts.

    Cross-MODE records (ISSUE 14): a tier constructed with ``kv_quant``
    refuses records whose snapshot was taken under the OTHER page-pool
    dtype — a bf16 snapshot scattered into an int8 pool (or vice versa)
    would serve garbage KV. Refusal is quarantine-STYLE: the record is
    set aside as ``*.crossmode`` (it is valid, just for a different
    serving mode — distinct from corruption), counted on
    ``finchat_quant_dequant_fallbacks_total``, and the conversation
    cold-starts. The startup sweep applies the same check, so a process
    restarted under a flipped ``engine.kv_quant`` sets every stale-mode
    record aside once, up front.

    Startup sweeps the directory: ``.tmp`` orphans from a mid-write crash
    are deleted, records whose header or size don't parse are quarantined,
    and the survivors rebuild the key index (LRU-ordered by mtime), so a
    restarted process resumes conversations warm.

    Writes are WRITE-BEHIND by default (``async_writes``): a record's
    serialize + write + fsync is seconds-class I/O at real model sizes,
    and the spill call sites sit inside the scheduler's event loop — the
    same stall class PR 6 moved off-loop with ``revive_async`` — so
    ``spill``/``discard`` enqueue onto ONE worker thread (FIFO, so a
    discard can never be overtaken by an older write of the same key) and
    return immediately. Snapshot arrays are safe to hand across: they are
    never mutated in place (truncation REPLACES them — the
    ``export_entry`` contract). ``load`` and ``flush`` drain the queue
    first, and the graceful drain's ``spill_all`` flushes, so the
    SIGTERM path stays fully durable; a hard kill can additionally lose
    whatever was still queued — milliseconds of records, inside the
    existing "at most the mid-stream turn" window.
    """

    MAGIC = b"FSKV"
    VERSION = 2
    READABLE_VERSIONS = (1, 2)
    SUFFIX = ".skv"

    def __init__(self, path: str, budget_bytes: int, metrics=None,
                 async_writes: bool = True, kv_quant: str = ""):
        assert budget_bytes > 0
        self.path = Path(path)
        self.path.mkdir(parents=True, exist_ok=True)
        self.budget_bytes = budget_bytes
        # the page-pool dtype this tier serves ("" = native): records whose
        # snapshot was taken under the other mode are refused at load/sweep
        self.kv_quant = kv_quant
        self.metrics = metrics if metrics is not None else METRICS
        # key -> (filename, nbytes), LRU order (oldest first); guarded by
        # _lock — the writer thread updates it as records land
        self._index: OrderedDict[str, tuple[str, int]] = OrderedDict()
        self._resident = 0
        # key -> queued-write count: the index only reflects LANDED
        # records, so membership checks must also see in-flight writes
        # (a just-spilled, RAM-evicted entry would otherwise read as
        # absent and cold-start), and load() need only pay the queue
        # barrier when ITS key is actually pending
        self._pending: dict[str, int] = {}
        self._lock = threading.Lock()
        self._writer = (
            ThreadPoolExecutor(max_workers=1, thread_name_prefix="skv-spill")
            if async_writes else None
        )
        self._sweep()

    # --- introspection ---------------------------------------------------
    def __len__(self) -> int:
        with self._lock:
            return len(self._index)

    def __contains__(self, key: str) -> bool:
        with self._lock:
            return key in self._index or key in self._pending

    @property
    def resident_bytes(self) -> int:
        with self._lock:
            return self._resident

    def _publish_gauges(self) -> None:
        self.metrics.set_gauge("finchat_durability_disk_resident_bytes", self._resident)
        self.metrics.set_gauge("finchat_durability_disk_entries", len(self._index))

    @staticmethod
    def _fname(key: str) -> str:
        # the key is user-derived (conversation id + role suffix): hash it
        # so it can never escape the directory or exceed filename limits
        return hashlib.sha1(key.encode()).hexdigest() + SessionDiskTier.SUFFIX

    # --- record (de)serialization ---------------------------------------
    @staticmethod
    def _serialize(key: str, token_ids: np.ndarray, prefix_len: int,
                   snap: tuple | None, kv_gap: int = 0,
                   kv_sink: int = 0) -> bytes:
        token_ids = np.ascontiguousarray(token_ids, np.int32)
        chunks = [token_ids.tobytes()]
        specs: list[dict | None] | None = None
        if snap is not None:
            specs = []
            for a in snap:
                if a is None:
                    specs.append(None)
                    continue
                a = np.ascontiguousarray(a)
                specs.append({"dtype": _dtype_name(a.dtype), "shape": list(a.shape)})
                chunks.append(a.tobytes())
        payload = b"".join(chunks)
        header = json.dumps({
            "key": key,
            "prefix_len": int(prefix_len),
            "n_tokens": int(token_ids.shape[0]),
            "snap": specs,
            "kv": snap_kv_mode(snap),
            # bounded-KV entries (ISSUE 15): evicted-token gap between the
            # pinned sink and the surviving window, and the absolute sink
            # end it inserts at. Additive v2 fields — records without
            # them (pre-ISSUE-15, and all v1) read as 0
            "kv_gap": int(kv_gap),
            "kv_sink": int(kv_sink),
            "payload_len": len(payload),
            "crc": zlib.crc32(payload),
        }).encode()
        return (SessionDiskTier.MAGIC + bytes([SessionDiskTier.VERSION])
                + len(header).to_bytes(4, "big") + header + payload)

    @staticmethod
    def _read_header(raw: bytes) -> tuple[dict, int]:
        """(header, payload offset); raises ValueError on any anomaly."""
        if raw[:4] != SessionDiskTier.MAGIC:
            raise ValueError("bad magic")
        if raw[4] not in SessionDiskTier.READABLE_VERSIONS:
            raise ValueError(f"unknown record version {raw[4]}")
        hlen = int.from_bytes(raw[5:9], "big")
        header = json.loads(raw[9 : 9 + hlen].decode())
        off = 9 + hlen
        if len(raw) - off != header["payload_len"]:
            raise ValueError("truncated record")
        return header, off

    @staticmethod
    def _header_kv_mode(header: dict) -> str:
        """A record's KV quant mode: the v2 ``kv`` stamp, or (v1 records)
        derived from whether scale-plane specs are present."""
        if "kv" in header:
            return header["kv"]
        specs = header.get("snap")
        if specs and len(specs) > 2 and specs[2] is not None:
            return "int8"
        return ""

    @staticmethod
    def _deserialize(raw: bytes) -> dict:
        header, off = SessionDiskTier._read_header(raw)
        payload = raw[off:]
        if zlib.crc32(payload) != header["crc"]:
            raise ValueError("payload checksum mismatch")
        n = header["n_tokens"]
        token_ids = np.frombuffer(payload, np.int32, count=n)
        pos = n * 4
        snap = None
        if header["snap"] is not None:
            arrs = []
            for spec in header["snap"]:
                if spec is None:
                    arrs.append(None)
                    continue
                dt = resolve_dtype(spec["dtype"])
                count = int(np.prod(spec["shape"])) if spec["shape"] else 1
                arrs.append(
                    np.frombuffer(payload, dt, count=count, offset=pos)
                    .reshape(spec["shape"])
                )
                pos += count * dt.itemsize
            snap = tuple(arrs)
        return {
            "conversation_id": header["key"],
            "token_ids": token_ids,
            "prefix_len": int(header["prefix_len"]),
            "snap": snap,
            "kv_gap": int(header.get("kv_gap", 0)),
            "kv_sink": int(header.get("kv_sink", 0)),
        }

    # --- write path ------------------------------------------------------
    def spill(self, key: str, token_ids: np.ndarray, prefix_len: int,
              snap: tuple | None, kv_gap: int = 0, kv_sink: int = 0) -> bool:
        """Record one entry (atomic write-rename), then LRU-evict records
        past the byte budget. Write-behind: the serialize + fsync runs on
        the writer thread and this returns immediately (True = accepted);
        a failed write (disk full, injected ``disk.spill`` fault) logs and
        counts on ``finchat_durability_spill_failures_total`` — the
        serving path never fails, and never waits, on durability I/O."""
        if self._writer is not None:
            with self._lock:
                self._pending[key] = self._pending.get(key, 0) + 1
            self._writer.submit(self._write_record, key, token_ids,
                                prefix_len, snap, kv_gap, kv_sink)
            return True
        return self._write_record(key, token_ids, prefix_len, snap, kv_gap,
                                  kv_sink)

    def _unpend(self, key: str) -> None:
        """One queued write for ``key`` finished (landed or failed)."""
        if self._writer is None:
            return
        with self._lock:
            n = self._pending.get(key, 0) - 1
            if n <= 0:
                self._pending.pop(key, None)
            else:
                self._pending[key] = n

    def _write_record(self, key: str, token_ids: np.ndarray, prefix_len: int,
                      snap: tuple | None, kv_gap: int = 0,
                      kv_sink: int = 0) -> bool:
        """Writer-thread body (inline when ``async_writes`` is off)."""
        fname = self._fname(key)
        final = self.path / fname
        tmp = self.path / (fname + ".tmp")
        try:
            inject("disk.spill", key=key)
            blob = self._serialize(key, token_ids, prefix_len, snap, kv_gap,
                                   kv_sink)
            with open(tmp, "wb") as f:
                f.write(blob)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, final)
        except Exception as e:
            logger.error("session disk tier: spill of %s failed: %s", key, e)
            self.metrics.inc("finchat_durability_spill_failures_total")
            tmp.unlink(missing_ok=True)
            self._unpend(key)
            return False
        victims: list[tuple[str, str, int]] = []
        with self._lock:
            old = self._index.pop(key, None)
            if old is not None:
                self._resident -= old[1]
            self._index[key] = (fname, len(blob))
            self._resident += len(blob)
            n = self._pending.get(key, 0) - 1
            if n <= 0:
                self._pending.pop(key, None)
            else:
                self._pending[key] = n
            while self._resident > self.budget_bytes and len(self._index) > 1:
                victim_key, (victim_fname, victim_bytes) = next(iter(self._index.items()))
                del self._index[victim_key]
                self._resident -= victim_bytes
                victims.append((victim_key, victim_fname, victim_bytes))
        self.metrics.inc("finchat_durability_spills_total")
        self.metrics.inc("finchat_durability_spilled_bytes_total", len(blob))
        for victim_key, victim_fname, victim_bytes in victims:
            (self.path / victim_fname).unlink(missing_ok=True)
            self.metrics.inc("finchat_durability_disk_evictions_total")
            logger.debug("session disk tier: evicted %s (LRU, %d bytes)",
                         victim_key, victim_bytes)
        self._publish_gauges()
        return True

    def discard(self, key: str) -> None:
        """Drop a key's record. Rides the writer queue (FIFO), so it can
        never be overtaken by an older queued write of the same key — and
        ``load`` flushes first, so a discarded record is unreachable the
        moment any reader could look for it."""
        if self._writer is not None:
            # pending too: a load between enqueue and unlink must barrier
            # and observe the pop, not read the doomed record
            with self._lock:
                self._pending[key] = self._pending.get(key, 0) + 1
            self._writer.submit(self._discard_now, key)
        else:
            self._discard_now(key)

    def _discard_now(self, key: str) -> None:
        with self._lock:
            entry = self._index.pop(key, None)
            if entry is not None:
                self._resident -= entry[1]
        self._unpend(key)
        if entry is not None:
            (self.path / entry[0]).unlink(missing_ok=True)
            self._publish_gauges()

    def flush(self) -> None:
        """Wait for every queued write/discard to land (graceful drain;
        read-side ops that must observe prior writes). FIFO barrier: the
        single worker makes one no-op submission a full drain."""
        if self._writer is not None:
            self._writer.submit(lambda: None).result()  # finchat-lint: disable=event-loop-blocking -- FIFO barrier by contract: reached only from the SIGTERM drain (must exit fully durable) and the per-key pending-write restore gate (ROBUSTNESS §5)

    def close(self) -> None:
        if self._writer is not None:
            self._writer.shutdown(wait=True)
            self._writer = None

    # --- read path -------------------------------------------------------
    def load(self, key: str) -> dict | None:
        """Read, verify, and decode one record: an ``export_entry``-shaped
        payload, or None (absent / quarantined). A hit refreshes LRU
        recency; the record stays on disk (the RAM copy may be evicted or
        lost again before the next spill overwrites it)."""
        with self._lock:
            pending = key in self._pending
        if pending:
            # barrier only when THIS key has a queued write: a full-queue
            # flush on every RAM-miss admission would stall the scheduler
            # loop behind every unrelated spill in flight
            self.flush()
        with self._lock:
            entry = self._index.get(key)
        if entry is None:
            return None
        try:
            inject("disk.restore", key=key)
            raw = (self.path / entry[0]).read_bytes()
            header, _off = self._read_header(raw)
            if header.get("snap") and self._header_kv_mode(header) != self.kv_quant:
                # valid record, WRONG page-pool dtype: scattering it into
                # this engine's pool would serve garbage KV — set it aside
                # (quarantine-style, distinct suffix) and cold-start
                self._refuse_crossmode(key, self._header_kv_mode(header))
                return None
            payload = self._deserialize(raw)
            if payload["conversation_id"] != key:
                raise ValueError("record key mismatch")
        except Exception as e:
            logger.error(
                "session disk tier: record for %s unreadable (%s); "
                "quarantining — conversation cold-starts", key, e,
            )
            self._quarantine(key)
            return None
        with self._lock:
            if key in self._index:
                self._index.move_to_end(key)
        return payload

    def _refuse_crossmode(self, key: str, record_mode: str,
                          fname: str | None = None) -> None:
        """Set aside a valid record written under the OTHER KV quant mode
        (``*.crossmode``; counted as a dequant fallback — the engine falls
        back to recomputing the prefix instead of serving stored KV).
        Distinct from :meth:`_quarantine`: the record is not corrupt, and
        the counter separates mode flips from data damage."""
        with self._lock:
            entry = self._index.pop(key, None)
            if entry is not None:
                fname, nbytes = entry
                self._resident -= nbytes
        if fname is not None:
            src = self.path / fname
            try:
                os.replace(src, self.path / (fname + ".crossmode"))
            except OSError:
                src.unlink(missing_ok=True)
        logger.warning(
            "session disk tier: record for %s was written under "
            "kv_quant=%r, this engine serves kv_quant=%r; set aside — "
            "conversation cold-starts", key, record_mode, self.kv_quant,
        )
        self.metrics.inc("finchat_quant_dequant_fallbacks_total")
        self._publish_gauges()

    def _quarantine(self, key: str, fname: str | None = None) -> None:
        with self._lock:
            entry = self._index.pop(key, None)
            if entry is not None:
                fname, nbytes = entry
                self._resident -= nbytes
        if fname is not None:
            src = self.path / fname
            try:
                os.replace(src, self.path / (fname + ".quarantine"))
            except OSError:
                src.unlink(missing_ok=True)
        self.metrics.inc("finchat_durability_quarantines_total")
        # flight recorder (ISSUE 12): a corrupt record is silent data loss
        # from the client's point of view (cold resume) — the black box
        # records which key, when, and what the serving plane was doing
        TRACER.anomaly("record_quarantine",
                       args={"key": key, "file": fname})
        self._publish_gauges()

    # --- startup ---------------------------------------------------------
    def _sweep(self) -> None:
        """Rebuild the index from the directory: delete ``.tmp`` orphans
        (a crash mid-write), quarantine records whose header or size don't
        parse (full CRC verification is deferred to load — the sweep stays
        O(header) per record), index the rest LRU-ordered by mtime."""
        found: list[tuple[float, str, str, int]] = []  # (mtime, key, fname, nbytes)
        for p in self.path.iterdir():
            name = p.name
            if name.endswith(".tmp"):
                p.unlink(missing_ok=True)  # orphaned partial write
                continue
            if not name.endswith(self.SUFFIX):
                continue  # quarantined or foreign file
            try:
                with open(p, "rb") as f:  # finchat-lint: disable=event-loop-blocking -- constructor-time directory sweep: runs once at process start, before the scheduler loop exists
                    head = f.read(9)
                    if (head[:4] != self.MAGIC
                            or head[4] not in self.READABLE_VERSIONS):
                        raise ValueError("bad magic/version")
                    hlen = int.from_bytes(head[5:9], "big")
                    header = json.loads(f.read(hlen).decode())
                size = p.stat().st_size
                if size != 9 + hlen + header["payload_len"]:
                    raise ValueError("size mismatch")
                if header.get("snap") and self._header_kv_mode(header) != self.kv_quant:
                    # a restart under a flipped engine.kv_quant: set every
                    # stale-mode record aside once, up front (same check
                    # load() applies; sweeping keeps the index honest)
                    self._refuse_crossmode(header["key"],
                                           self._header_kv_mode(header),
                                           fname=name)
                    continue
                found.append((p.stat().st_mtime, header["key"], name, size))
            except Exception as e:
                logger.error("session disk tier: sweeping out bad record %s "
                             "(%s)", name, e)
                try:
                    os.replace(p, self.path / (name + ".quarantine"))
                except OSError:
                    p.unlink(missing_ok=True)
                self.metrics.inc("finchat_durability_quarantines_total")
        for _mtime, key, fname, nbytes in sorted(found):
            self._index[key] = (fname, nbytes)
            self._resident += nbytes
        if self._index:
            logger.info("session disk tier: %d resumable records (%d bytes) "
                        "at %s", len(self._index), self._resident, self.path)
        self._publish_gauges()


@dataclass
class SessionEntry:
    """One retired conversation's resumable KV.

    ``token_ids`` holds the ``n_tokens`` tokens whose KV the entry covers —
    always a whole-page multiple, split as ``[0, prefix_len)`` living in the
    referenced shared-prefix pages and ``[prefix_len, n_tokens)`` in the
    host snapshot. ``prefix_entry`` (a scheduler ``_PrefixEntry`` or None)
    carries one reference held for the entry's lifetime; the cache's
    ``on_drop`` callback is where the scheduler releases it.

    ``kv_gap`` (bounded-KV serving, ISSUE 15): tokens the eviction policy
    dropped between the pinned sink (``kv_sink`` absolute tokens) and the
    surviving window when the sequence retired. The snapshot then covers
    only the SURVIVING pages — ``n_tokens - kv_gap - prefix_len`` tokens —
    while ``token_ids`` still spans the full absolute range (the evicted
    tokens' ids must match the next turn's prompt for the surviving KV to
    be valid). A gapped entry resumes whole (sink+window intact) when the
    prompt extends past its span unchanged; on divergence the windowed
    remainder is unusable (it attended to the now-mismatched history) and
    ``match`` salvages at most the pre-gap sink region as an ordinary
    gap-free prefix.
    """

    conversation_id: str
    token_ids: np.ndarray  # int32 [n_tokens]
    prefix_entry: Any | None = None
    prefix_pages: list[int] = field(default_factory=list)  # device page ids, referenced
    prefix_len: int = 0  # tokens covered by prefix_pages (page multiple)
    snap: tuple | None = None  # host page arrays covering [prefix_len, n_tokens)
    kv_gap: int = 0  # bounded-KV evicted tokens (page multiple; 0 = exact)
    # absolute position the gap inserts at (the sink end; page multiple):
    # tokens below it attended only EARLIER sink tokens, so they remain a
    # valid ordinary prefix even when the windowed remainder is stale —
    # the divergence salvage in match() leans on this. 0 when kv_gap is 0.
    kv_sink: int = 0

    @property
    def n_tokens(self) -> int:
        return len(self.token_ids)

    @property
    def nbytes(self) -> int:
        return snap_nbytes(self.snap)

    def own_pages_for(self, matched: int, page_size: int) -> int:
        """How many snapshot pages a ``matched``-token resume restores
        (the evicted gap has no pages)."""
        return max(0, matched - self.prefix_len - self.kv_gap) // page_size


class SessionKVCache:
    """Host-RAM LRU of ``SessionEntry`` keyed by conversation id.

    Single-task by design (the scheduler loop is the only caller), so no
    locking; the byte budget counts host snapshot bytes only — referenced
    shared-prefix pages live in device HBM under their own owner and are
    already accounted there.
    """

    def __init__(self, budget_bytes: int, page_size: int,
                 on_drop: Callable[[SessionEntry], None] | None = None,
                 metrics=None, disk: SessionDiskTier | None = None,
                 fabric=None, fabric_replica: str | None = None):
        assert budget_bytes > 0 and page_size > 0
        self.budget_bytes = budget_bytes
        self.page_size = page_size
        self._on_drop = on_drop
        # a fleet replica passes METRICS.labeled(replica=...) so its cache
        # series separate from its siblings'; default is the global registry
        self.metrics = metrics if metrics is not None else METRICS
        # durability plane (ISSUE 7): entries write THROUGH to the disk
        # tier at put — not only at eviction — so a process kill loses at
        # most the mid-stream turn, and a RAM miss falls through to disk
        # via the scheduler (_restore_session_from_disk, which re-links
        # shared heads); None = host-RAM only (pre-ISSUE-7 behavior)
        self.disk = disk
        # warm-state fabric (ISSUE 17): when set, ``disk`` IS the fleet's
        # shared tier and this cache keeps the fabric's global RAM index
        # current — put notes this replica as the key's holder, drops
        # forget it (holder-guarded) — so the router's deeper-entry-wins
        # migration is an index lookup instead of a pairwise scan
        self.fabric = fabric
        self.fabric_replica = fabric_replica
        self._entries: OrderedDict[str, SessionEntry] = OrderedDict()
        self._resident_bytes = 0
        self._publish_gauges()

    # --- introspection ---------------------------------------------------
    def __len__(self) -> int:
        return len(self._entries)

    @property
    def resident_bytes(self) -> int:
        return self._resident_bytes

    def get(self, conversation_id: str) -> SessionEntry | None:
        return self._entries.get(conversation_id)

    def _publish_gauges(self) -> None:
        self.metrics.set_gauge("finchat_session_cache_resident_bytes", self._resident_bytes)
        self.metrics.set_gauge("finchat_session_cache_entries", len(self._entries))

    # --- write path ------------------------------------------------------
    def put(self, entry: SessionEntry, *, spill: bool = True) -> bool:
        """Insert (replacing any previous entry for the conversation),
        then LRU-evict others until the byte budget holds. Returns False —
        and drops nothing from RAM — when the entry alone exceeds the
        budget. With a disk tier, the entry writes through to its record
        file either way: an over-budget entry is still resumable from disk
        (``fit_payload`` trims it back under the RAM budget at restore —
        the millions-of-idle-conversations case, ROADMAP item 4), and a
        stored one survives a process kill. ``spill=False`` is the
        disk-RESTORE path: the bytes just came off that record, so
        rewriting them would double every restore's I/O for nothing."""
        if spill:
            self._spill(entry)
        if entry.nbytes > self.budget_bytes:
            logger.warning(
                "session cache: entry for %s (%d bytes) exceeds budget %d; not stored",
                entry.conversation_id, entry.nbytes, self.budget_bytes,
            )
            return False
        old = self._entries.pop(entry.conversation_id, None)
        if old is not None:
            self._drop(old)
        self._entries[entry.conversation_id] = entry
        self._resident_bytes += entry.nbytes
        while self._resident_bytes > self.budget_bytes:
            victim_id, victim = next(iter(self._entries.items()))
            del self._entries[victim_id]
            self._drop(victim)
            self.metrics.inc("finchat_session_cache_evictions_total")
            logger.debug("session cache: evicted %s (LRU, %d bytes)",
                         victim_id, victim.nbytes)
        if self.fabric is not None and entry.conversation_id in self._entries:
            # the insert may itself have been LRU-evicted above
            self.fabric.note(entry.conversation_id, self.fabric_replica,
                             entry.n_tokens)
        self._publish_gauges()
        return True

    def discard(self, conversation_id: str) -> None:
        """Drop a conversation's entry from BOTH tiers — used when the
        bytes move elsewhere (fleet migration / drain handoff): a disk
        twin left behind could later restore on a replica the conversation
        no longer routes to."""
        if self.disk is not None:
            self.disk.discard(conversation_id)
        entry = self._entries.pop(conversation_id, None)
        if entry is not None:
            self._drop(entry)
            self._publish_gauges()

    def drop_local(self, conversation_id: str) -> None:
        """Drop the RAM copy ONLY — the fabric-migration counterpart of
        ``discard``: the bytes just moved to another replica whose put
        wrote through to the SHARED tier, so deleting the disk record
        here would erase the record the target just refreshed (the two
        ride the same single writer queue)."""
        entry = self._entries.pop(conversation_id, None)
        if entry is not None:
            self._drop(entry)
            self._publish_gauges()

    def clear(self) -> None:
        for entry in list(self._entries.values()):
            self._drop(entry)
        self._entries.clear()
        self._publish_gauges()

    def discard_if(self, pred: Callable[[SessionEntry], bool]) -> int:
        """Drop every entry matching ``pred``; returns how many. Used by
        prefix retirement: an entry referencing a retired head pins that
        head's DEVICE pages (the whole point of the refcount), but after a
        rollover the head can never match again — idle conversations would
        otherwise pin retired-head HBM indefinitely."""
        victims = [e for e in self._entries.values() if pred(e)]
        for entry in victims:
            del self._entries[entry.conversation_id]
            self._drop(entry)
        if victims:
            self._publish_gauges()
        return len(victims)

    def _drop(self, entry: SessionEntry) -> None:
        self._resident_bytes -= entry.nbytes
        entry.snap = None
        if self._on_drop is not None:
            self._on_drop(entry)
        if self.fabric is not None:
            # holder-guarded: a migration target that already noted its
            # fresher copy keeps its claim when the source drops here
            self.fabric.forget(entry.conversation_id, self.fabric_replica)

    # --- disk tier (ISSUE 7) ---------------------------------------------
    def _spill(self, entry: SessionEntry) -> bool:
        """Write one entry's record through to the disk tier (no-op
        without one). The record is the ``export_entry`` payload shape —
        ``prefix_len`` travels, the head's device pages never do — so a
        restore re-links against the restoring scheduler's own live
        head."""
        if self.disk is None or entry.n_tokens == 0:
            return False
        return self.disk.spill(
            entry.conversation_id, entry.token_ids, entry.prefix_len,
            entry.snap, entry.kv_gap, entry.kv_sink,
        )

    def spill_all(self) -> int:
        """Re-spill every resident entry (graceful-shutdown drain): puts
        already wrote through, so this is a retry pass for any spill that
        failed transiently plus a freshness pass for entries truncated
        since. Flushes the write-behind queue — the SIGTERM path exits
        fully durable. Returns how many records were written."""
        n = sum(1 for e in self._entries.values() if self._spill(e))
        if self.disk is not None:
            self.disk.flush()
        return n

    def fit_payload(self, payload: dict) -> dict | None:
        """Trim a disk/exported payload to the largest page-whole prefix
        whose host bytes fit the RAM budget, so an over-budget record is
        still (partially) resumable instead of being refused by ``put``
        on every turn — per-turn full-record churn that never warms
        anything. Snapshot pages are uniform-size, so the byte budget maps
        directly to a page count. Returns the payload untouched when it
        fits, a trimmed copy when a prefix does, or None when nothing
        does (no shared head, not one page under budget) — the caller
        should drop the record rather than retry forever."""
        snap = payload["snap"]
        nbytes = snap_nbytes(snap)
        if nbytes <= self.budget_bytes:
            return payload
        if payload.get("kv_gap"):
            # a bounded entry is whole-or-not (see SessionEntry): trimming
            # would cut the window the gap semantics depend on. Bounded
            # snapshots are at most sink+window pages, so one exceeding
            # the RAM budget is a configuration problem, not a hot path.
            return None
        prefix_len = int(payload["prefix_len"])
        own_pages = (len(payload["token_ids"]) - prefix_len) // self.page_size
        keep = int(own_pages * self.budget_bytes // nbytes)
        if keep <= 0 and prefix_len <= 0:
            return None
        trimmed = dict(payload)
        trimmed["token_ids"] = np.asarray(payload["token_ids"], np.int32)[
            : prefix_len + keep * self.page_size
        ]
        trimmed["snap"] = _slice_snap(snap, keep)
        logger.warning(
            "session cache: disk record for %s (%d bytes) exceeds RAM "
            "budget %d; trimmed to %d of %d own pages for a partial warm "
            "resume", payload["conversation_id"], nbytes, self.budget_bytes,
            keep, own_pages,
        )
        return trimmed

    # --- cross-replica migration (serve/fleet.py; ISSUE 6) ---------------
    def export_entry(self, conversation_id: str) -> dict | None:
        """Portable, device-independent image of one conversation's entry
        for cross-replica handoff: token ids + the host snapshot arrays.
        The referenced shared-prefix DEVICE pages are NOT exportable — the
        payload carries only ``prefix_len`` (the head's tokens are
        ``token_ids[:prefix_len]``) so the importer can re-link against
        its OWN live registration of the same head
        (scheduler ``import_session_entry``). Snapshot arrays are shared
        by reference, never mutated in place (truncation replaces them),
        so export is O(1) — no host memcpy of the KV bytes. The entry
        stays resident here; the caller discards it once adopted."""
        entry = self._entries.get(conversation_id)
        if entry is None or entry.n_tokens == 0:
            return None
        return {
            "conversation_id": conversation_id,
            "token_ids": np.array(entry.token_ids, copy=True),
            "prefix_len": int(entry.prefix_len),
            "snap": entry.snap,
            "kv_gap": int(entry.kv_gap),
            "kv_sink": int(entry.kv_sink),
        }

    def import_entry(self, payload: dict, *, prefix_entry: Any | None = None,
                     prefix_pages: list[int] | None = None,
                     spill: bool = True) -> bool:
        """Adopt an exported entry. ``prefix_entry``/``prefix_pages`` is
        the importer's OWN live twin of the exported shared head —
        resolved, validated, and refcounted by the scheduler — covering
        exactly ``payload['prefix_len']`` tokens; both empty only when
        the payload has no head. Returns ``put``'s verdict (the caller
        un-references the head on False, mirroring ``_maybe_offload``)."""
        prefix_len = int(payload["prefix_len"])
        assert (prefix_len == 0) == (prefix_entry is None)
        entry = SessionEntry(
            conversation_id=payload["conversation_id"],
            token_ids=np.asarray(payload["token_ids"], np.int32),
            prefix_entry=prefix_entry,
            prefix_pages=list(prefix_pages or []),
            prefix_len=prefix_len,
            snap=payload["snap"],
            kv_gap=int(payload.get("kv_gap", 0)),
            kv_sink=int(payload.get("kv_sink", 0)),
        )
        return self.put(entry, spill=spill)

    # --- read path -------------------------------------------------------
    def match(self, conversation_id: str, prompt_ids: list[int]) -> tuple[SessionEntry | None, int]:
        """Longest resumable prefix of ``prompt_ids`` held for this
        conversation: the common token prefix with the entry, floored to
        whole pages, capped so at least one prompt token remains to prefill
        (the admission commit needs real last-token logits — same rule as
        the shared-prefix matcher). A hit refreshes LRU recency.

        Divergence is handled HERE, eagerly: if the new turn's tokens split
        from the stored stream before its end, the entry is truncated to
        the common prefix — the tail belongs to a history this conversation
        no longer has, so it could only ever serve stale KV."""
        entry = self._entries.get(conversation_id)
        if entry is None or not prompt_ids:
            return None, 0
        page = self.page_size
        prompt = np.asarray(prompt_ids, np.int32)
        n = min(entry.n_tokens, len(prompt))
        neq = np.nonzero(entry.token_ids[:n] != prompt[:n])[0]
        common = int(neq[0]) if neq.size else n
        if entry.kv_gap:
            # bounded entries (ISSUE 15) resume WHOLE when the prompt
            # extends past their span unchanged (sink+window intact)...
            if not neq.size:
                if common >= entry.n_tokens and len(prompt) - 1 >= entry.n_tokens:
                    self._entries.move_to_end(conversation_id)
                    return entry, entry.n_tokens
                # a prompt that merely STOPS SHORT (no divergence) can't
                # use the entry but hasn't staled it — keep it intact for
                # the turn that extends past the span
                return None, 0
            # ...and on DIVERGENCE salvage only the pre-gap sink region:
            # the windowed remainder attended to the evicted tokens, so a
            # mismatch anywhere below it stales it beyond repair — but
            # sink tokens attended only earlier sink tokens, so they
            # truncate into a perfectly ordinary gap-free prefix entry
            # (the RAG workload diverges every turn where the previous
            # turn's retrieved block sat; without the salvage a bounded
            # conversation would never resume warm).
            salvage = (min(common, entry.kv_sink) // page) * page
            entry.kv_gap = 0
            entry.kv_sink = 0
            self._truncate(entry, min(salvage, entry.n_tokens))
            if entry.n_tokens == 0:
                return None, 0
            # the salvaged entry continues through the ordinary gap-free
            # matching below; the original common may overshoot it
            common = min(common, entry.n_tokens)
        if common < entry.n_tokens:
            self._truncate(entry, (common // page) * page)
            if entry.n_tokens == 0:
                return None, 0
        cap = ((len(prompt) - 1) // page) * page
        matched = min((common // page) * page, cap)
        if matched <= 0:
            return None, 0
        self._entries.move_to_end(conversation_id)
        return entry, matched

    def _truncate(self, entry: SessionEntry, n_tokens: int) -> None:
        """Cut an entry down to a page-aligned token count (divergence).
        An entry truncated to nothing is dropped entirely."""
        assert n_tokens % self.page_size == 0 and n_tokens <= entry.n_tokens
        self.metrics.inc("finchat_session_cache_truncations_total")
        before = entry.nbytes
        entry.token_ids = entry.token_ids[:n_tokens]
        if n_tokens <= entry.prefix_len:
            # the divergence falls inside the shared head: keep only the
            # matched whole head pages (still referenced, still read-only)
            entry.prefix_len = n_tokens
            entry.prefix_pages = entry.prefix_pages[: n_tokens // self.page_size]
            entry.snap = None
        else:
            entry.snap = _slice_snap(
                entry.snap, (n_tokens - entry.prefix_len) // self.page_size
            )
        self._resident_bytes += entry.nbytes - before
        if entry.n_tokens == 0:
            del self._entries[entry.conversation_id]
            self._drop(entry)
        self._publish_gauges()
