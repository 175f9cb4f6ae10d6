"""Second-level embedding storage backend with quantized codecs.

Models the paper's split between DRAM (first-level centroids, cache) and
SD-card storage (precomputed heavy-cluster embeddings).  The "disk" flavor
actually writes .npz files so persistence is real; the "memory" flavor keeps
payloads in a dict (fast unit tests).  Either way the *edge* latency of a
load comes from the cost model, not this machine's SSD.

Codecs (beyond-paper: MobileRAG-style on-device memory budgeting): the
stored payload can be narrowed below fp32 —

  fp32   bit-exact roundtrip (default; keeps the Table-4 parity claims)
  fp16   half-precision embeddings                       (2x fewer bytes)
  int8   per-row symmetric int8 + fp16 scales, reusing
         models/quantization.py's KV-cache scheme        (~3.9x fewer bytes)
  pq     product quantization (core/pq.py): one uint8 code per subspace
         against a backend-held codebook                 (8-32x fewer bytes)

PQ CODEC: payloads are ``{"codes": uint8 (n, m), "cbv": version}`` — the
codebook itself lives on the backend (``self.pq``), trained once at index
build (``train_pq``) and persisted next to on-disk roots as
``pq_codebook.npz`` so a reopened root still decodes.  ``cbv`` pins each
blob to the codebook version that encoded it; after a drift retrain
(version bump) a stale blob fails its read like a corrupt one —
quarantine-dropped WITHOUT retries (the mismatch is deterministic) so the
resolver regenerates at full precision and self-heals a fresh copy under
the new codebook.  A ``put`` with no codebook yet lazily trains one on
that put's rows (standalone-backend convenience; the index trains on the
full corpus before its first put).

MODES: ``memory`` (dict), ``disk`` (.npz files), and ``memmap`` — disk
layout and crash-safe atomic writes, but reads return ``np.memmap`` views
into the uncompressed npz members instead of loading arrays, so a
100M-vector tier's payloads are never resident: ``get_many_raw`` hands the
slab packer memmap-backed payloads it slices, not copies.  Checksum
verification still touches every byte (it pages the mapping through the
OS cache — the integrity guarantee is kept deliberately); the win is that
nothing is ever *retained* in process memory.

``get``/``get_many`` always return contiguous f32 matrices (decode on
load); ``stored_bytes``/``total_bytes`` report the encoded payload size in
memory mode and the ``os.stat`` on-disk size in disk/memmap modes (what
the medium actually stores and a load actually streams) — byte accounting
NEVER reads payload data.

RAW-CODEC LOADS (``get_many_raw``): the packed-slab scoring engine scores
fp16/int8 clusters directly in their storage representation (fused
in-kernel dequantization, kernels/slab_topk), so it loads payloads
*undecoded*: ``get_many_raw`` returns each cluster's codec payload dict
exactly as stored — ``{"emb": f32|f16}`` or ``{"q": int8, "scale": f16}``
— with a missing key yielding ``None``, same ordering contract as
``get_many``.  Callers must treat the payload arrays as READ-ONLY (memory
mode hands out the live stored arrays, not copies); ``payload_rows`` gives
the row count without decoding and ``decode`` turns a raw payload into the
f32 matrix ``get`` would have returned.

FAILURE MODEL (core/faults.py): every ``put`` stores a per-key CRC-32
checksum alongside the payload (a ``"crc"`` member, stripped before any
payload reaches a caller and excluded from byte accounting) and every load
verifies it, so a bit-flipped or truncated blob — real or injected — is
always detected, never silently scored.  ``get`` / ``get_many`` /
``get_many_raw`` retry failed reads up to ``retry_limit`` times with
exponential backoff (``backoff_base_s * 2**attempt`` MODELED edge seconds,
no real sleep); per-key costs land in the caller-supplied
:class:`~repro.core.faults.IOOutcome` list and aggregate in ``io_stats``.
After retries exhaust, the read degrades to a missing key (``None`` /
``KeyError``) so callers fall back to regeneration; a checksum failure
that survives every retry additionally QUARANTINE-DROPS the blob, so the
resolver's Alg. 1 self-heal re-persists a fresh copy instead of re-reading
rot forever.  A genuinely absent key is returned immediately without
retries (today's semantics).  Setting ``self.faults`` to a
:class:`~repro.core.faults.FaultInjector` makes reads go through its
deterministic fault/stall model; ``None`` (default) leaves the fast path
byte-identical to the pre-fault-model backend.

Disk-mode ``put`` is CRASH-SAFE: the payload is written to a temp file in
the same directory and atomically ``os.replace``d over the key's path, so
an interrupted write can never leave a torn payload behind (and a torn
file from an older writer is caught by the checksum / container parse and
degrades like any corrupt blob).

UNDO COPIES (crash recovery, core/durability.py): once :meth:`track_undo`
is on, the first write of an op that replaces or deletes a key's blob
first hard-links the old blob to ``<blob>.undo``.  The index drops the
copies (:meth:`discard_undo`) when the op's WAL record has landed; a copy
that outlives a crash is resolved by :meth:`resolve_undo` — put back if it
is the blob the durable state claims, deleted otherwise.

MULTI-TENANCY: keys may be plain ints (single-tenant, the historical
contract — paths and accounting unchanged) or ``(tenant, cid)`` tuples.
Tuple keys land in per-tenant ``tenant_<name>/`` subdirectories on disk and
are first-class dict keys in memory mode; ``keys()`` enumerates both forms.
:class:`TenantStorageView` gives one tenant an int-keyed facade over a
shared backend so :class:`~repro.core.edgerag.EdgeRAGIndex` needs no
changes to run on shared storage.  ``budget_bytes`` imposes a SHARED byte
budget across every key (all tenants): a ``put`` that would exceed it
refuses — stores nothing, returns 0, bumps ``io_stats["put_rejected"]`` —
and the caller keeps the cluster on the regeneration path.  The budget is
an in-process quota over bytes this instance knows about (its own writes
plus lazily discovered pre-existing blobs), not an fsck of the root.

ROOT COLLISION GUARD: memory mode has always refused to touch a filesystem
root at all (``_path`` raises).  Disk mode extends that safety to WRITERS:
the first ``put`` claims the ``(root, namespace)`` slot in a process-wide
registry, and a second live instance writing to the same slot raises
``RuntimeError`` instead of silently interleaving blobs with the first.
Reopening a root read-only (metadata/get) never claims, and a dead writer's
claim expires with it.  Pass distinct ``namespace=`` strings (each gets its
own subdirectory of ``root``) to intentionally co-locate several stores
under one root.
"""
from __future__ import annotations

import os
import re
import struct
import tempfile
import weakref
import zipfile
import zlib
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from repro.core.faults import (CorruptPayloadError, FaultInjector,
                               InjectedFault, IOOutcome)
from repro.core.pq import (PQCodebook, codebook_from_payload,
                           codebook_to_payload, pq_decode, pq_encode,
                           train_pq)

CODECS = ("fp32", "fp16", "int8", "pq")
MODES = ("memory", "disk", "memmap")
_CODEBOOK_FILE = "pq_codebook.npz"


class StaleCodebookError(CorruptPayloadError):
    """PQ payload encoded under an older codebook version.  Deterministic —
    retrying the read cannot help — so reads skip the backoff ladder and
    quarantine-drop immediately, putting the cluster on the regen +
    re-encode self-heal path."""

_CLUSTER_FILE = re.compile(r"^cluster_(\d+)\.npz$")
_TENANT_DIR = re.compile(r"^tenant_([A-Za-z0-9._-]+)$")
_UNDO_FILE = re.compile(r"^cluster_(\d+)\.npz\.undo$")
_UNDO = ".undo"
# tmp and undo files OUR writers leave behind when a put/train dies
# mid-write or an op dies before its WAL record — the only such names
# clear() is allowed to sweep (foreign files stay)
_STALE_TMP = re.compile(
    r"^((cluster_\d+\.npz|pq_codebook\.npz)\.tmp|cluster_\d+\.npz\.undo)$")
_NAMESPACE_RE = re.compile(r"^[A-Za-z0-9._-]*$")
_CHECKSUM_KEY = "crc"

#: blob key: a bare cluster id, or ``(tenant, cid)`` on a shared backend
StorageKey = Union[int, Tuple[str, int]]


def _file_crc(path: str) -> Optional[int]:
    """The ``"crc"`` member of a blob file, or None if it cannot be read."""
    try:
        with np.load(path) as z:
            return int(np.asarray(z[_CHECKSUM_KEY]).reshape(-1)[0])
    except Exception:
        return None


def payload_checksum(payload: Dict[str, np.ndarray]) -> int:
    """CRC-32 over the payload's arrays (name, dtype, shape, data) — any
    single bit flip or truncation changes it."""
    crc = 0
    for name in sorted(payload):
        a = np.ascontiguousarray(payload[name])
        crc = zlib.crc32(f"{name}:{a.dtype.str}:{a.shape}".encode(), crc)
        crc = zlib.crc32(a.view(np.uint8).reshape(-1), crc)
    return crc


class StorageBackend:
    """Keyed blob store for per-cluster embedding matrices."""

    # live disk WRITERS by (realpath(root), namespace); weakrefs so a
    # garbage-collected writer releases its claim (module docstring)
    _disk_claims: Dict[Tuple[str, str], "weakref.ref[StorageBackend]"] = {}

    def __init__(self, mode: str = "memory", root: Optional[str] = None,
                 codec: str = "fp32", *, retry_limit: int = 3,
                 backoff_base_s: float = 0.002, namespace: str = "",
                 budget_bytes: Optional[int] = None, pq_m: int = 8):
        assert mode in MODES, f"mode must be one of {MODES}, got {mode}"
        assert codec in CODECS, f"codec must be one of {CODECS}, got {codec}"
        assert _NAMESPACE_RE.match(namespace), \
            f"namespace must match [A-Za-z0-9._-]*, got {namespace!r}"
        self.mode = mode
        self.codec = codec
        self.namespace = namespace
        self.budget_bytes = budget_bytes
        self.pq_m = pq_m
        self.pq: Optional[PQCodebook] = None
        self._mem: Dict[StorageKey, Dict[str, np.ndarray]] = {}
        self._nbytes: Dict[StorageKey, int] = {}    # stored payload bytes
        self._crcs: Dict[StorageKey, int] = {}      # payload CRC at put time
        # keys with an undo copy since the last discard; None: not tracking
        self._undo: Optional[set] = None
        self.root: Optional[str] = None
        self._base: Optional[str] = None            # root[/namespace]
        if mode != "memory":
            self.root = root or tempfile.mkdtemp(prefix="edgerag_store_")
            self._base = (os.path.join(self.root, namespace) if namespace
                          else self.root)
            os.makedirs(self._base, exist_ok=True)
            cb_path = os.path.join(self._base, _CODEBOOK_FILE)
            if os.path.exists(cb_path):      # reopened root: restore codebook
                with np.load(cb_path) as z:
                    self.pq = codebook_from_payload(
                        {name: z[name] for name in z.files})
        # failure model (module docstring): injector hook + retry policy
        self.faults: Optional[FaultInjector] = None
        self.retry_limit = retry_limit
        self.backoff_base_s = backoff_base_s
        self.io_stats: Dict[str, float] = {
            "reads": 0, "verified": 0, "failed_attempts": 0, "retries": 0,
            "exhausted": 0, "corrupt_dropped": 0, "backoff_s": 0.0,
            "stall_s": 0.0, "put_rejected": 0}

    # ---- codec ----------------------------------------------------------
    def _encode(self, emb: np.ndarray) -> Dict[str, np.ndarray]:
        emb = np.ascontiguousarray(emb, np.float32)
        if self.codec == "fp32":
            return {"emb": emb}
        if self.codec == "fp16":
            return {"emb": emb.astype(np.float16)}
        if self.codec == "pq":
            if self.pq is None:      # standalone-backend convenience: the
                self.train_pq(emb)   # index trains on the corpus at build
            return {"codes": pq_encode(self.pq, emb),
                    "cbv": np.array([self.pq.version], np.int32)}
        from repro.models.quantization import quantize_rows
        q, scale = quantize_rows(emb)
        return {"q": q, "scale": scale}

    def _decode(self, payload: Dict[str, np.ndarray]) -> np.ndarray:
        if "q" in payload:
            from repro.models.quantization import dequantize_rows
            return dequantize_rows(payload["q"], payload["scale"])
        if "codes" in payload:
            if self.pq is None:
                raise CorruptPayloadError(
                    "pq payload but no codebook on this backend")
            return pq_decode(self.pq, payload["codes"])
        return np.ascontiguousarray(payload["emb"], np.float32)

    def decode(self, payload: Dict[str, np.ndarray]) -> np.ndarray:
        """Decode a raw payload (from ``get_many_raw``) to f32 (n, d)."""
        return self._decode(payload)

    @staticmethod
    def payload_rows(payload: Dict[str, np.ndarray]) -> int:
        """Row count of a raw payload without decoding it."""
        if "q" in payload:
            return len(payload["q"])
        if "codes" in payload:
            return len(payload["codes"])
        return len(payload["emb"])

    # ---- PQ codebook lifecycle ------------------------------------------
    def train_pq(self, embeddings: np.ndarray, *, iters: int = 12,
                 seed: int = 0) -> PQCodebook:
        """(Re)train the product-quantization codebook on ``embeddings``.

        First call -> version 0; later calls (drift retrains) bump the
        version, which invalidates every blob encoded under the old one:
        their next read raises :class:`StaleCodebookError`, quarantine-
        drops, and the resolver self-heals a fresh copy.  On-disk modes
        persist the codebook next to the root so reopens decode."""
        version = 0 if self.pq is None else self.pq.version + 1
        self.pq = train_pq(embeddings, m=self.pq_m, iters=iters, seed=seed,
                           version=version)
        if self.mode != "memory":
            self._claim_root()
            cb_path = os.path.join(self._base, _CODEBOOK_FILE)
            tmp = cb_path + ".tmp"
            try:
                with open(tmp, "wb") as f:
                    np.savez(f, **codebook_to_payload(self.pq))
                os.replace(tmp, cb_path)
            except BaseException:
                if os.path.exists(tmp):
                    os.remove(tmp)
                raise
        return self.pq

    # ---- filesystem (disk mode only) ------------------------------------
    def _path(self, key: StorageKey) -> str:
        if self.root is None:
            raise RuntimeError(
                "memory-mode StorageBackend has no filesystem root")
        if isinstance(key, tuple):
            tenant, cid = key
            return os.path.join(self._base, f"tenant_{tenant}",
                                f"cluster_{cid}.npz")
        return os.path.join(self._base, f"cluster_{key}.npz")

    def _claim_root(self):
        """First write claims the ``(root, namespace)`` slot; a second LIVE
        writer on the same slot is a collision, not a merge (module
        docstring).  Read-only reopens never claim."""
        slot = (os.path.realpath(self.root), self.namespace)
        ref = StorageBackend._disk_claims.get(slot)
        owner = ref() if ref is not None else None
        if owner is not None and owner is not self:
            raise RuntimeError(
                f"storage root collision: another live StorageBackend is "
                f"already writing to root={self.root!r} "
                f"namespace={self.namespace!r}; their blobs would silently "
                f"overwrite each other — give each writer its own "
                f"namespace= (or root)")
        StorageBackend._disk_claims[slot] = weakref.ref(self)

    def _load(self, key: int) -> Optional[Dict[str, np.ndarray]]:
        """Raw physical read (checksum member included).  A present-but-
        unreadable disk blob (torn container) raises
        :class:`CorruptPayloadError` instead of propagating zip/npy
        internals."""
        if self.mode == "memory":
            return self._mem.get(key)
        path = self._path(key)
        if not os.path.exists(path):
            return None
        if self.mode == "memmap":
            return self._load_memmap(path, key)
        try:
            with np.load(path) as z:
                return {name: z[name] for name in z.files}
        except Exception as e:
            raise CorruptPayloadError(f"unreadable blob for key {key}: {e}")

    @staticmethod
    def _load_memmap(path: str, key: StorageKey
                     ) -> Dict[str, np.ndarray]:
        """Open an npz as read-only ``np.memmap`` views, one per member.

        ``np.savez`` stores members uncompressed (ZIP_STORED), so each
        array's data is a contiguous byte range of the container file:
        local-file-header offset + 30 + name/extra lengths + the .npy
        header.  Mapping that range gives a zero-copy view — nothing is
        read until a consumer touches pages (CRC verification does, by
        design; slab packing slices first and touches only what it
        scores)."""
        try:
            out: Dict[str, np.ndarray] = {}
            with zipfile.ZipFile(path) as z, open(path, "rb") as raw:
                for info in z.infolist():
                    name = info.filename
                    if name.endswith(".npy"):
                        name = name[:-4]
                    with z.open(info) as f:
                        version = np.lib.format.read_magic(f)
                        read_header = getattr(
                            np.lib.format,
                            "read_array_header_%d_%d" % version)
                        shape, fortran, dtype = read_header(f)
                        header_len = f.tell()
                    if info.compress_type != zipfile.ZIP_STORED or fortran:
                        raise ValueError(
                            f"member {name} is not memmap-able")
                    # the central directory's header_offset points at the
                    # local file header: 30 fixed bytes, then name + extra
                    raw.seek(info.header_offset + 26)
                    n_name, n_extra = struct.unpack("<HH", raw.read(4))
                    offset = (info.header_offset + 30 + n_name + n_extra
                              + header_len)
                    if int(np.prod(shape, dtype=np.int64)) == 0:
                        out[name] = np.empty(shape, dtype)
                    else:
                        out[name] = np.memmap(path, mode="r", dtype=dtype,
                                              shape=tuple(shape),
                                              offset=offset)
            return out
        except Exception as e:
            raise CorruptPayloadError(f"unreadable blob for key {key}: {e}")

    # ---- verified / retried reads ----------------------------------------
    def _read_once(self, key: int, outcome: IOOutcome
                   ) -> Optional[Dict[str, np.ndarray]]:
        """One read attempt: physical load, injected faults, checksum
        verification.  Returns the CRC-stripped payload, ``None`` for a
        genuinely absent key, or raises the attempt's failure."""
        payload = self._load(key)
        if payload is None:
            return None
        if self.faults is not None:
            payload = self.faults.perturb(key, payload, outcome)
        crc = payload.get(_CHECKSUM_KEY)
        if crc is None:                 # legacy blob: unverifiable
            return payload
        body = {k: v for k, v in payload.items() if k != _CHECKSUM_KEY}
        if payload_checksum(body) != int(np.asarray(crc).reshape(-1)[0]):
            raise CorruptPayloadError(key)
        if "codes" in body and self.pq is not None:
            cbv = int(np.asarray(body.get("cbv", -1)).reshape(-1)[0])
            if cbv != self.pq.version:
                raise StaleCodebookError(key)
        self.io_stats["verified"] += 1
        return body

    def _load_checked(self, key: int, outcome: IOOutcome
                      ) -> Optional[Dict[str, np.ndarray]]:
        """Bounded retry-with-exponential-backoff around :meth:`_read_once`
        (module docstring).  Backoff is MODELED edge seconds recorded on
        ``outcome``, never a real sleep."""
        self.io_stats["reads"] += 1
        last_err: Optional[str] = None
        for attempt in range(self.retry_limit + 1):
            if attempt:
                backoff = self.backoff_base_s * (2 ** (attempt - 1))
                outcome.retries += 1
                outcome.backoff_s += backoff
                self.io_stats["retries"] += 1
                self.io_stats["backoff_s"] += backoff
            try:
                payload = self._read_once(key, outcome)
            except StaleCodebookError:
                # deterministic mismatch: retries cannot help, fall through
                # to the quarantine-drop below without burning backoff
                last_err = "corrupt"
                self.io_stats["failed_attempts"] += 1
                break
            except CorruptPayloadError:
                last_err = "corrupt"
            except InjectedFault as e:
                last_err = "io" if isinstance(e, IOError) else "missing"
            else:
                if payload is not None:
                    self.io_stats["stall_s"] += outcome.stall_s
                    return payload
                # genuinely absent (the blob is not there, faulty or not):
                # retrying cannot help — degrade immediately, as before
                outcome.ok = False
                outcome.error = "missing"
                self.io_stats["stall_s"] += outcome.stall_s
                return None
            self.io_stats["failed_attempts"] += 1
        outcome.ok = False
        outcome.error = last_err
        self.io_stats["exhausted"] += 1
        self.io_stats["stall_s"] += outcome.stall_s
        if last_err == "corrupt":
            # quarantine-drop the rotten blob: the caller regenerates and
            # the resolver's Alg. 1 self-heal re-persists a fresh copy
            self.io_stats["corrupt_dropped"] += 1
            self.delete(key)
        return None

    # ---- public API ------------------------------------------------------
    def put(self, key: StorageKey, embeddings: np.ndarray) -> int:
        """Returns the stored byte size — exact encoded payload bytes in
        memory mode, the ``os.stat`` on-disk file size in disk/memmap
        modes (container + checksum included: what the medium holds) — or
        0 if the shared ``budget_bytes`` refused the write (nothing
        stored; the caller keeps the cluster on the regen path).  On-disk
        writes are atomic: temp file + ``os.replace``, so a crash
        mid-write never tears the blob."""
        payload = self._encode(embeddings)
        nbytes = sum(a.nbytes for a in payload.values())
        if self.budget_bytes is not None:
            used = sum(self._nbytes.values()) - self._nbytes.get(key, 0)
            if used + nbytes > self.budget_bytes:
                self.io_stats["put_rejected"] += 1
                return 0
        crc = payload_checksum(payload)
        stored = dict(payload)
        stored[_CHECKSUM_KEY] = np.array([crc], np.uint32)
        if self.mode == "memory":
            self._mem[key] = stored
        else:
            self._claim_root()
            path = self._path(key)
            os.makedirs(os.path.dirname(path), exist_ok=True)
            self._stash_undo(key, path)
            tmp = path + ".tmp"
            try:
                with open(tmp, "wb") as f:
                    np.savez(f, **stored)
                os.replace(tmp, path)
            except BaseException:
                if os.path.exists(tmp):
                    os.remove(tmp)
                raise
            nbytes = os.stat(path).st_size
        self._nbytes[key] = nbytes
        self._crcs[key] = crc
        return self._nbytes[key]

    def get(self, key: int) -> np.ndarray:
        payload = self._load_checked(key, IOOutcome(key))
        if payload is None:
            raise KeyError(key)
        return self._decode(payload)

    def get_many(self, keys: Sequence[int],
                 outcomes: Optional[List[IOOutcome]] = None
                 ) -> List[Optional[np.ndarray]]:
        """Batched load, results in ``keys`` order; a missing key — or one
        whose reads exhausted their retries — yields ``None`` (callers fall
        back to regeneration instead of crashing).  ``outcomes`` collects
        one :class:`IOOutcome` per key (retries / stall / backoff)."""
        out: List[Optional[np.ndarray]] = []
        for key in keys:
            o = IOOutcome(key)
            payload = self._load_checked(key, o)
            if outcomes is not None:
                outcomes.append(o)
            out.append(None if payload is None else self._decode(payload))
        return out

    def get_many_raw(self, keys: Sequence[int],
                     outcomes: Optional[List[IOOutcome]] = None
                     ) -> List[Optional[Dict[str, np.ndarray]]]:
        """Batched load of UNDECODED codec payloads, results in ``keys``
        order, missing/exhausted key -> ``None`` (see module docstring:
        payloads are read-only; the slab scorer consumes them via fused
        dequant).  Checksums are verified and stripped; ``outcomes``
        collects per-key :class:`IOOutcome` records."""
        out: List[Optional[Dict[str, np.ndarray]]] = []
        for key in keys:
            o = IOOutcome(key)
            out.append(self._load_checked(key, o))
            if outcomes is not None:
                outcomes.append(o)
        return out

    def payload_crc(self, key: StorageKey) -> int:
        """CRC-32 of the stored payload, WITHOUT reading the payload data:
        the ``"crc"`` member recorded at put time (cached per key; a fresh
        instance on an old root lazily reads just that member from the
        container).  Raises ``KeyError`` for an absent or unreadable blob.
        This is what crash recovery (core/durability.py) compares against
        the manifest's recorded checksum to detect a blob that was
        replaced mid-op before the WAL record landed."""
        if key in self._crcs:
            return self._crcs[key]
        if self.mode == "memory":
            if key not in self._mem:
                raise KeyError(key)
            crc = int(np.asarray(
                self._mem[key][_CHECKSUM_KEY]).reshape(-1)[0])
        else:
            crc = _file_crc(self._path(key))
            if crc is None:
                raise KeyError(key)
        self._crcs[key] = crc
        return crc

    # ---- undo copies (module docstring) ------------------------------------
    def track_undo(self):
        """From now on keep an undo copy of each blob an op replaces or
        deletes, until :meth:`discard_undo`.  Memory mode has nothing to
        recover after a crash, so it keeps none."""
        if self.mode != "memory" and self._undo is None:
            self._undo = set()

    def _stash_undo(self, key: StorageKey, path: str):
        if self._undo is None or key in self._undo \
                or not os.path.exists(path):
            return                  # only the op's FIRST write is pre-op
        undo = path + _UNDO
        if os.path.exists(undo):    # a copy whose op committed: dead
            os.remove(undo)
        os.link(path, undo)
        self._undo.add(key)

    def discard_undo(self):
        """The op's WAL record landed: its undo copies are dead weight."""
        for key in self._undo or ():
            undo = self._path(key) + _UNDO
            if os.path.exists(undo):
                os.remove(undo)
        if self._undo:
            self._undo.clear()

    def undo_keys(self) -> List[StorageKey]:
        """Keys with an undo copy on disk: an op died before its record."""
        return [] if self.mode == "memory" else self._scan(_UNDO_FILE)

    def resolve_undo(self, key: StorageKey, durable_crc: Optional[int]
                     ) -> bool:
        """Recovery: put the undo copy of ``key`` back if it is the blob
        the durable state claims (``durable_crc``) and the live blob is
        not — returns True — else delete the copy."""
        path = self._path(key)
        undo = path + _UNDO
        back = (durable_crc is not None and _file_crc(path) != durable_crc
                and _file_crc(undo) == durable_crc)
        if back:
            os.replace(undo, path)
            self._crcs[key] = durable_crc
            self._nbytes[key] = os.stat(path).st_size
        else:
            os.remove(undo)
        return back

    def delete(self, key: int):
        self._nbytes.pop(key, None)
        self._crcs.pop(key, None)
        if self.mode == "memory":
            self._mem.pop(key, None)
            return
        path = self._path(key)
        if os.path.exists(path):
            self._stash_undo(key, path)
            os.remove(path)
        # a crashed put can strand its temp file next to the blob: sweep it
        # so the directory never accumulates torn garbage
        if os.path.exists(path + ".tmp"):
            os.remove(path + ".tmp")

    def clear(self):
        """Drop every stored cluster (index rebuilds) — plus, on disk
        roots, the persisted PQ codebook file and any stale ``.tmp`` or
        ``.undo`` files a crashed put or op left behind, so a rebuild on
        this root never decodes against a leftover codebook version or
        trips over torn garbage.
        (The in-memory codebook is kept: a rebuild's ``train_pq`` bumps
        its version, preserving the stale-blob invalidation semantics.)"""
        for key in self.keys():
            self.delete(key)
        self._nbytes.clear()
        self._crcs.clear()
        if self._undo:
            self._undo.clear()
        if self.mode == "memory":
            return
        cb_path = os.path.join(self._base, _CODEBOOK_FILE)
        if os.path.exists(cb_path):
            os.remove(cb_path)
        dirs = [self._base] + [
            os.path.join(self._base, e) for e in os.listdir(self._base)
            if _TENANT_DIR.match(e)
            and os.path.isdir(os.path.join(self._base, e))]
        for d in dirs:
            for f in os.listdir(d):
                if _STALE_TMP.match(f):
                    os.remove(os.path.join(d, f))

    def __contains__(self, key: StorageKey) -> bool:
        if self.mode == "memory":
            return key in self._mem
        return os.path.exists(self._path(key))

    def keys(self) -> List[StorageKey]:
        if self.mode == "memory":
            return list(self._mem)
        return self._scan(_CLUSTER_FILE)

    def _scan(self, pattern: "re.Pattern") -> List[StorageKey]:
        # foreign files in a user-supplied root are not ours to touch:
        # only our file names in the base directory and its tenant_<name>/
        # subdirectories are enumerated
        out: List[StorageKey] = [
            int(m.group(1)) for m in
            (pattern.match(f) for f in os.listdir(self._base)) if m]
        for entry in os.listdir(self._base):
            td = _TENANT_DIR.match(entry)
            if not td or not os.path.isdir(os.path.join(self._base, entry)):
                continue
            tenant = td.group(1)
            for f in os.listdir(os.path.join(self._base, entry)):
                m = pattern.match(f)
                if m:
                    out.append((tenant, int(m.group(1))))
        return out

    def stored_bytes(self, key: int) -> int:
        """Stored bytes of one cluster (what a load streams): exact encoded
        bytes in memory mode, the on-disk file size otherwise."""
        if key not in self._nbytes:       # e.g. fresh instance on an old root
            if self.mode == "memory":
                if key not in self._mem:
                    raise KeyError(key)
                self._nbytes[key] = sum(
                    a.nbytes for name, a in self._mem[key].items()
                    if name != _CHECKSUM_KEY)
            else:
                self._nbytes[key] = self._disk_payload_nbytes(key)
        return self._nbytes[key]

    def _disk_payload_nbytes(self, key: int) -> int:
        """On-disk size via ``os.stat`` — byte accounting must never READ
        the payload (at memmap scale, opening and parsing every blob to
        count bytes would page the whole tier through memory).  The stat
        size is also the honest number: container framing and the CRC
        member are bytes the medium stores and a load streams."""
        try:
            return os.stat(self._path(key)).st_size
        except OSError:
            raise KeyError(key)

    def total_bytes(self) -> int:
        return sum(self.stored_bytes(k) for k in self.keys())

    def tenant_bytes(self, tenant: str) -> int:
        """Encoded bytes held under one tenant's ``(tenant, cid)`` keys."""
        return sum(self.stored_bytes(k) for k in self.keys()
                   if isinstance(k, tuple) and k[0] == tenant)


class TenantStorageView:
    """One tenant's int-keyed facade over a SHARED :class:`StorageBackend`.

    Every cluster id is rewritten to ``(tenant, cid)`` before it reaches
    the backend, so an :class:`~repro.core.edgerag.EdgeRAGIndex` holding a
    view is oblivious to its neighbors while all tenants' blobs compete for
    the backend's one ``budget_bytes`` quota.  ``keys`` / ``clear`` /
    ``total_bytes`` are scoped to this tenant; ``io_stats`` and ``faults``
    are the backend's (the device has one storage medium — faults and IO
    accounting are physical, not per-tenant)."""

    def __init__(self, backend: StorageBackend, tenant: str):
        self.backend = backend
        self.tenant = str(tenant)

    def _k(self, cid: int) -> Tuple[str, int]:
        return (self.tenant, int(cid))

    # shared physical properties ------------------------------------------
    @property
    def mode(self) -> str:
        return self.backend.mode

    @property
    def codec(self) -> str:
        return self.backend.codec

    @property
    def root(self) -> Optional[str]:
        return self.backend.root

    @property
    def io_stats(self) -> Dict[str, float]:
        return self.backend.io_stats

    @property
    def faults(self) -> Optional[FaultInjector]:
        return self.backend.faults

    @faults.setter
    def faults(self, injector: Optional[FaultInjector]):
        self.backend.faults = injector

    @property
    def pq(self) -> Optional[PQCodebook]:
        """The SHARED product-quantization codebook (one physical medium,
        one codebook — tenants share it like they share ``io_stats``)."""
        return self.backend.pq

    def train_pq(self, embeddings: np.ndarray, **kw) -> PQCodebook:
        return self.backend.train_pq(embeddings, **kw)

    # key-mapped blob API --------------------------------------------------
    def put(self, cid: int, embeddings: np.ndarray) -> int:
        return self.backend.put(self._k(cid), embeddings)

    def get(self, cid: int) -> np.ndarray:
        try:
            return self.backend.get(self._k(cid))
        except KeyError:
            raise KeyError(cid)

    def get_many(self, cids: Sequence[int],
                 outcomes: Optional[List[IOOutcome]] = None
                 ) -> List[Optional[np.ndarray]]:
        return self.backend.get_many([self._k(c) for c in cids], outcomes)

    def get_many_raw(self, cids: Sequence[int],
                     outcomes: Optional[List[IOOutcome]] = None
                     ) -> List[Optional[Dict[str, np.ndarray]]]:
        return self.backend.get_many_raw([self._k(c) for c in cids],
                                         outcomes)

    def delete(self, cid: int):
        self.backend.delete(self._k(cid))

    def __contains__(self, cid: int) -> bool:
        return self._k(cid) in self.backend

    def keys(self) -> List[int]:
        return [k[1] for k in self.backend.keys()
                if isinstance(k, tuple) and k[0] == self.tenant]

    def track_undo(self):
        self.backend.track_undo()

    def discard_undo(self):
        self.backend.discard_undo()

    def undo_keys(self) -> List[int]:
        return [k[1] for k in self.backend.undo_keys()
                if isinstance(k, tuple) and k[0] == self.tenant]

    def resolve_undo(self, cid: int, durable_crc: Optional[int]) -> bool:
        return self.backend.resolve_undo(self._k(cid), durable_crc)

    def clear(self):
        """Drop THIS tenant's blobs only (its index rebuilds)."""
        for cid in self.keys():
            self.delete(cid)

    def stored_bytes(self, cid: int) -> int:
        try:
            return self.backend.stored_bytes(self._k(cid))
        except KeyError:
            raise KeyError(cid)

    def payload_crc(self, cid: int) -> int:
        try:
            return self.backend.payload_crc(self._k(cid))
        except KeyError:
            raise KeyError(cid)

    def total_bytes(self) -> int:
        return self.backend.tenant_bytes(self.tenant)

    def decode(self, payload: Dict[str, np.ndarray]) -> np.ndarray:
        return self.backend.decode(payload)

    @staticmethod
    def payload_rows(payload: Dict[str, np.ndarray]) -> int:
        return StorageBackend.payload_rows(payload)
