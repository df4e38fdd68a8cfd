"""Immutable segment: memmap load + padded device residency.

Reference parity: pinot-segment-local/.../indexsegment/immutable/
ImmutableSegmentLoader.java:101 (mmap all index buffers via PinotDataBuffer,
per-column DataSource map). The TPU-native replacement for PinotDataBuffer
(pinot-segment-spi/.../memory/PinotDataBuffer.java:60 — LArray/Unsafe
off-heap mmap) is np.memmap for zero-copy host reads feeding
jax.device_put as pow2-padded device arrays; padding bounds the number of
distinct XLA compilations (bucketed shapes) and validity is re-derived on
device as iota < n_docs (masks replace RoaringBitmap docId sets).
"""
from __future__ import annotations

import itertools
import json
import os
import threading
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..spi.schema import DataType, Schema
from ..utils.devmem import global_device_memory
from ..utils.heat import global_segment_heat
from . import segdir
from .builder import METADATA_FILE
from .dictionary import Dictionary

MIN_BUCKET = 1 << 10

# monotonically unique id per loaded segment (never reused, unlike id())
_SEG_UID = itertools.count(1)


def bucket_for(n_docs: int, min_bucket: int = MIN_BUCKET) -> int:
    b = min_bucket
    while b < n_docs:
        b <<= 1
    return b


class ColumnMeta:
    def __init__(self, name: str, d: Dict[str, Any]):
        self.name = name
        self.data_type = DataType(d["dataType"])
        self.field_type = d["fieldType"]
        self.encoding = d["encoding"]  # DICT | RAW
        self.fwd_dtype = np.dtype(d["fwdDtype"])
        self.fwd_format = d.get("fwdFormat", "PLAIN")  # |BITPACK|COMPRESSED
        self.bits = d.get("bits")
        self.codec = d.get("codec")
        self.raw_size = d.get("rawSize")
        self.cardinality = d.get("cardinality", 0)
        self.is_sorted = d.get("isSorted", False)
        self.min = d.get("min")
        self.max = d.get("max")
        self.has_nulls = d.get("hasNulls", False)
        self.dict_format = d.get("dictFormat")
        self.dict_dtype = d.get("dictDtype")
        self.partitions = d.get("partitions")
        self.single_value = d.get("singleValue", True)
        self.max_values = d.get("maxValues")  # MV: padded row width
        # secondary indexes: kind -> extra metadata (index/registry.py)
        self.indexes: Dict[str, Any] = d.get("indexes", {})

    @property
    def has_dict(self) -> bool:
        return self.encoding == "DICT"


class ImmutableSegment:
    """A loaded immutable segment: host memmaps + lazy device cache."""

    def __init__(self, seg_dir: str, read_mode: str = "mmap"):
        self.dir = seg_dir
        with open(os.path.join(seg_dir, METADATA_FILE)) as fh:
            self.metadata = json.load(fh)
        self.name: str = self.metadata["segmentName"]
        self.n_docs: int = self.metadata["totalDocs"]
        self.schema = Schema.from_dict(self.metadata["schema"])
        self.columns: Dict[str, ColumnMeta] = {
            name: ColumnMeta(name, d)
            for name, d in self.metadata["columns"].items()}
        self._read_mode = read_mode
        # process-unique load identity: caches that outlive the segment
        # object (engine/batch._STACK_CACHE) must key on THIS, not the
        # segment name — names recur across tables/reloads with the same
        # bucket, and a name-keyed device cache silently serves the old
        # table's data (found by the round-9 chaos soak)
        self.uid: int = next(_SEG_UID)
        self._index_readers: Dict[Tuple[str, str], Any] = {}
        self._fwd: Dict[str, np.ndarray] = {}
        self._dicts: Dict[str, Dictionary] = {}
        self._nulls: Dict[str, Optional[np.ndarray]] = {}
        # key: (name, bucket, sharding) — sharding None = default backend
        self._device: Dict[Tuple[str, int, Any], jax.Array] = {}
        # warm tier (engine/tier.py): padded host arrays kept after an
        # HBM demotion so re-promotion is one device_put, no re-pad.
        # Only populated while a tier budget is armed — unbounded runs
        # stay byte-for-byte the pre-tier behavior.
        self._warm: Dict[Tuple[str, int, Any], np.ndarray] = {}
        # residency lock: a cache insert (+ its devmem add) and a tier
        # demotion's drain (+ its devmem removes) must be atomic with
        # respect to each other, or a concurrent demote could drop an
        # array whose bytes were just registered — a permanently
        # orphaned devmem entry. Reads (device_col's dict.get) stay
        # lock-free: a racy miss only re-uploads.
        self._res_lock = threading.Lock()
        # upsert validDocIds (None = all docs valid); versioned so the
        # device-resident copy invalidates on update
        self.valid_docs: Optional[np.ndarray] = None
        self.valid_docs_version = 0
        # the id space of the server that holds this segment among
        # others (server/id_space.ServerIdSpace; None outside a server)
        self.server_ids = None
        if segdir.exists(seg_dir, "valid.bin"):
            bits = np.asarray(segdir.read_array(seg_dir, "valid.bin",
                                                np.uint8, mmap=False))
            self.valid_docs = np.unpackbits(bits)[: self.n_docs].astype(bool)
        from ..utils import leak
        leak.track(self, "segment", self.name)

    @classmethod
    def load(cls, seg_dir: str, read_mode: str = "mmap") -> "ImmutableSegment":
        return cls(seg_dir, read_mode)

    @property
    def format_version(self) -> str:
        """SegmentVersion lineage: "v1" (file per index) or "v3" (single
        packed columns.psf); numeric 1 is the pre-versioning spelling."""
        v = self.metadata.get("formatVersion", "v1")
        return "v1" if v in (1, "1", "v1") else str(v)

    # -- host access -------------------------------------------------------
    def fwd(self, col: str) -> np.ndarray:
        """Stored forward index (dict ids or raw values), host-side.

        PLAIN columns memmap zero-copy; BITPACK/COMPRESSED decode once
        through the native runtime (pinot_tpu.native) and cache."""
        if col not in self._fwd:
            m = self.columns[col]
            name = f"{col}.fwd.bin"
            if m.fwd_format == "BITPACK":
                from .. import native
                buf = np.ascontiguousarray(segdir.read_array(
                    self.dir, name, np.uint8))
                arr = native.fixedbit_unpack(buf, self.n_docs, m.bits)
            elif m.fwd_format == "COMPRESSED":
                from .. import native
                comp = np.ascontiguousarray(segdir.read_array(
                    self.dir, name, np.uint8))
                raw = native.decompress(comp, m.raw_size, m.codec)
                arr = raw.view(m.fwd_dtype)[: self.n_docs]
            else:
                shape = ((self.n_docs,) if m.single_value
                         else (self.n_docs, m.max_values))
                arr = segdir.read_array(self.dir, name, m.fwd_dtype,
                                        shape=shape,
                                        mmap=self._read_mode == "mmap")
            self._fwd[col] = arr
        return self._fwd[col]

    def dictionary(self, col: str) -> Optional[Dictionary]:
        m = self.columns[col]
        if not m.has_dict:
            return None
        if col not in self._dicts:
            if m.dict_format == "json":
                vals = segdir.read_json(self.dir, f"{col}.dict.json")
                self._dicts[col] = Dictionary(vals, m.data_type)
            else:
                vals = np.asarray(segdir.read_array(
                    self.dir, f"{col}.dict.bin", np.dtype(m.dict_dtype),
                    mmap=False))
                self._dicts[col] = Dictionary(vals, m.data_type)
        return self._dicts[col]

    def null_mask(self, col: str) -> Optional[np.ndarray]:
        m = self.columns[col]
        if not m.has_nulls:
            return None
        if col not in self._nulls:
            bits = np.asarray(segdir.read_array(
                self.dir, f"{col}.null.bin", np.uint8, mmap=False))
            self._nulls[col] = np.unpackbits(bits)[: self.n_docs].astype(bool)
        return self._nulls[col]

    def index_reader(self, col: str, kind: str):
        """Lazy secondary-index reader (StandardIndexes registry analog);
        None when the column has no such index."""
        m = self.columns.get(col)
        if m is None or kind not in m.indexes:
            return None
        key = (col, kind)
        if key not in self._index_readers:
            from .. import index as index_pkg
            reader = index_pkg.load_index(self.dir, col, kind,
                                          m.indexes[kind])
            if kind == "vector":
                # bind tier/devmem identity: the reader's device
                # residents account as (uid, col) in the `vector` pool
                # and its uploads admit THIS segment to the HBM tier
                reader.attach_owner(self, col)
            self._index_readers[key] = reader
        return self._index_readers[key]

    def raw_values(self, col: str) -> np.ndarray:
        """Decoded values (host-side; for selection results / oracles)."""
        m = self.columns[col]
        if m.encoding == "VECTOR":
            return np.asarray(self.index_reader(col, "vector").matrix)
        stored = self.fwd(col)
        if not m.single_value:
            d = self.dictionary(col)
            out = np.empty(self.n_docs, dtype=object)
            for i, row in enumerate(np.asarray(stored)):
                ids = row[row >= 0]
                out[i] = list(d.values_for(ids))
            return out
        if m.has_dict:
            return self.dictionary(col).values_for(np.asarray(stored))
        return np.asarray(stored)

    # -- device residency --------------------------------------------------
    @property
    def bucket(self) -> int:
        return bucket_for(self.n_docs)

    def _put(self, host: np.ndarray, sharding) -> jax.Array:
        """device_put honoring an explicit placement (mesh sharding or
        device, None = process default); bare placement is wrong when a
        query runs on a CPU mesh under a TPU default."""
        return jax.device_put(host, sharding)

    def _cache_device(self, key, arr: jax.Array,
                      host: Optional[np.ndarray] = None) -> jax.Array:
        """Every _device insert routes through here so the device-memory
        registry's live-byte gauges always reconcile with the cache —
        and so the HBM tier (engine/tier.py) sees every admission: the
        insert promotes this segment hot and enforces the shared budget.
        ``host`` is the uploaded host representation; while a tier
        budget is armed it is stashed warm for cheap re-promotion."""
        from ..engine.tier import global_tier
        with self._res_lock:
            self._device[key] = arr
            global_device_memory.add("segment_cols", (self.uid, key),
                                     int(arr.nbytes))
            if host is not None and global_tier.armed:
                old = self._warm.get(key)
                self._warm[key] = host
                global_tier.note_warm(
                    self.uid, int(getattr(host, "nbytes", 0))
                    - (int(old.nbytes) if old is not None else 0))
        # tier admission OUTSIDE _res_lock: enforcement may demote
        # OTHER segments (their _res_lock) — never nested under ours
        global_tier.admitted(self)
        return arr

    def device_col(self, col: str, bucket: Optional[int] = None,
                   sharding=None) -> jax.Array:
        """Padded device array for a column's stored representation.

        Dict ids upcast to int32 (byte-width storage is a host format detail;
        int32 is the TPU-friendly lane width). Raw columns keep their dtype.
        Pad value 0 — validity masks make padding inert.

        This is also the tier's transparent re-promotion path: a
        demoted segment's read misses the device cache, uploads from
        the warm host array when one is stashed (no re-pad) and lands
        byte-identical regardless of prior tier placement.
        """
        from ..engine.tier import global_tier
        bucket = bucket or self.bucket
        key = (col, bucket, sharding)
        hit = self._device.get(key)
        # observed device-cache hit ratio feeds the segment-heat table
        # (the tier's admission signal)
        global_segment_heat.device_access(self, hit is not None)
        # tier.evict chaos hook: may force-demote THIS segment mid-query
        # (a ref already fetched stays alive; later columns re-promote)
        global_tier.on_access(self)
        if hit is None:
            host = self._warm.get(key)
            if host is None:
                host = self.host_col_padded(col, bucket)
            hit = self._cache_device(key, self._put(host, sharding),
                                     host=host)
        return hit

    def host_col_padded(self, col: str, bucket: Optional[int] = None
                        ) -> np.ndarray:
        """The bucket-padded host representation device_col uploads —
        exposed separately so the streaming scan path (engine/pipeline.py)
        can double-buffer transfers WITHOUT populating the device cache."""
        bucket = bucket or self.bucket
        m = self.columns[col]
        host = np.asarray(self.fwd(col))
        if m.has_dict:
            host = host.astype(np.int32, copy=False)
        if bucket > self.n_docs:
            # MV columns pad rows with -1 (the padded-slot sentinel);
            # SV padding is inert under validity masks either way
            pad = np.full((bucket - self.n_docs,) + host.shape[1:],
                          -1 if not m.single_value else 0,
                          dtype=host.dtype)
            host = np.concatenate([host, pad])
        return host

    def device_cols(self, cols: List[str], bucket: Optional[int] = None,
                    sharding=None) -> Tuple[jax.Array, ...]:
        bucket = bucket or self.bucket
        return tuple(self.device_col(c, bucket, sharding=sharding)
                     for c in cols)

    def device_dict_values(self, col: str, sharding=None) -> jax.Array:
        """Device-resident sorted dictionary values (cached; used for
        id->value gathers inside kernels)."""
        # return the LOCAL ref, never re-read self._device: a
        # concurrent tier demotion may drop the key between insert and
        # return (the device_col discipline — a racy loser only
        # re-uploads next call, never KeyErrors)
        key = (f"__dict__{col}", 0, sharding)
        hit = self._device.get(key)
        if hit is None:
            vals = self._warm.get(key)
            if vals is None:
                m = self.columns[col]
                vals = np.asarray(self.dictionary(col).values,
                                  dtype=m.data_type.np_dtype)
            hit = self._cache_device(key, self._put(vals, sharding),
                                     host=vals)
        return hit

    def device_null_mask(self, col: str, bucket: Optional[int] = None,
                         sharding=None) -> jax.Array:
        bucket = bucket or self.bucket
        key = (f"__null__{col}", bucket, sharding)
        hit = self._device.get(key)
        if hit is None:
            padded = self._warm.get(key)
            if padded is None:
                nm = self.null_mask(col)
                padded = np.zeros(bucket, dtype=bool)
                if nm is not None:
                    padded[: len(nm)] = nm
            hit = self._cache_device(key, self._put(padded, sharding),
                                     host=padded)
        return hit  # local ref: a racy demotion must not KeyError

    def set_valid_docs(self, mask: Optional[np.ndarray]) -> None:
        self.valid_docs = mask
        self.valid_docs_version += 1
        # drop stale device AND warm copies (the warm stash must never
        # re-promote a superseded validity mask)
        with self._res_lock:
            for key in [k for k in self._device
                        if k[0].startswith("__valid__")]:
                del self._device[key]
                global_device_memory.remove("segment_cols",
                                            (self.uid, key))
            for key in [k for k in self._warm
                        if k[0].startswith("__valid__")]:
                old = self._warm.pop(key)
                from ..engine.tier import global_tier
                global_tier.note_warm(self.uid, -int(old.nbytes))

    def persist_valid_docs(self) -> None:
        """Snapshot validDocIds next to the segment (upsert snapshot analog,
        pinot-segment-local/.../upsert/ validDocIds persistence)."""
        path = os.path.join(self.dir, "valid.bin")
        if self.valid_docs is None:
            if os.path.exists(path):
                os.remove(path)
            return
        np.packbits(self.valid_docs).tofile(path)

    def device_valid_mask(self, bucket: Optional[int] = None,
                          sharding=None) -> jax.Array:
        bucket = bucket or self.bucket
        key = (f"__valid__v{self.valid_docs_version}", bucket, sharding)
        hit = self._device.get(key)
        if hit is None:
            padded = self._warm.get(key)
            if padded is None:
                padded = np.zeros(bucket, dtype=bool)
                if self.valid_docs is not None:
                    padded[: self.n_docs] = self.valid_docs
                else:
                    padded[: self.n_docs] = True
            hit = self._cache_device(key, self._put(padded, sharding),
                                     host=padded)
        return hit  # local ref: a racy demotion must not KeyError

    def demote_device(self, drop_warm: bool = False) -> None:
        """Tier demotion (engine/tier.py): drop the device residents
        and every stacked/cube copy containing this segment (the
        round-9 eviction discipline — a demotion that left a stacked
        copy resident would free nothing). The warm padded host arrays
        survive for cheap re-promotion unless ``drop_warm`` (host ->
        disk: the mmap is the only remaining copy). The drain is
        atomic vs concurrent inserts (_res_lock), so devmem can never
        track an array this demotion dropped."""
        with self._res_lock:
            for key in list(self._device):
                global_device_memory.remove("segment_cols",
                                            (self.uid, key))
            self._device.clear()
            if drop_warm:
                self._drop_warm_locked()
        from ..engine.batch import evict_stacks_containing
        evict_stacks_containing(self.name)
        from ..ops.plan_cache import global_cube_cache
        global_cube_cache.evict_containing(self.name)
        # vector-pool residents (index/vector.py) demote with the
        # segment too: the readers re-upload transparently on the next
        # search, byte-identically (their own lock discipline)
        for (c, kind), rd in list(self._index_readers.items()):
            if kind == "vector":
                rd.evict_device()

    def _drop_warm_locked(self) -> bool:  # holds-lock: _res_lock
        if not self._warm:
            return False
        from ..engine.tier import global_tier
        global_tier.note_warm(
            self.uid,
            -sum(int(a.nbytes) for a in self._warm.values()))
        self._warm.clear()  # jaxlint: ok unlocked-mutation
        return True

    def drop_warm(self) -> bool:
        """Release ONLY the warm host stash (engine/tier's warm-budget
        enforcement on segments that stay HOT — their device residents
        are untouched; the next demotion just re-pads from mmap).
        True when there was a stash to drop."""
        with self._res_lock:
            return self._drop_warm_locked()

    def evict_device(self) -> None:
        """Full unload: device + warm copies gone, tier state cold."""
        self.demote_device(drop_warm=True)
        from ..engine.tier import global_tier
        global_tier.on_evicted(self)

    def __repr__(self) -> str:
        return (f"ImmutableSegment({self.name!r}, docs={self.n_docs}, "
                f"cols={list(self.columns)})")
