"""Table/segment data manager: segment lifecycle on a server.

Reference parity: pinot-core/.../data/manager/BaseTableDataManager.java
(segment add/replace/remove with acquire/release refcounting) and
ServerQueryExecutorV1Impl.java:203-217 (acquire-all for a query). Python's
GIL + immutable segment objects let us replace Java's refcounting with
atomic dict swaps; a query captures a consistent snapshot list.
"""
from __future__ import annotations

import os
import threading
from typing import Dict, List, Optional

from ..segment.immutable import ImmutableSegment


class TableDataManager:
    def __init__(self, table_name: str, table_config=None):
        self.table_name = table_name
        self.table_config = table_config  # TableConfig | None
        self._segments: Dict[str, ImmutableSegment] = {}
        self._lock = threading.Lock()
        self._reload_lock = threading.Lock()
        self._schema = None
        # optional mesh-resident DistributedTable (parallel/distributed.py):
        # the in-process broker and a mesh-holding server node answer
        # kernel-plan aggregations from it (engine/serving.execute_on_mesh);
        # the server node keeps it equal to the loaded segments
        self.distributed = None

    def set_distributed(self, distributed) -> None:
        self.distributed = distributed

    def add_segment(self, segment: ImmutableSegment) -> None:
        with self._lock:
            self._segments = {**self._segments, segment.name: segment}

    def add_segment_dir(self, seg_dir: str) -> ImmutableSegment:
        seg = ImmutableSegment.load(seg_dir)
        self.add_segment(seg)
        return seg

    def add_table_dir(self, table_dir: str) -> List[ImmutableSegment]:
        """Load every segment directory under a table directory."""
        out = []
        for name in sorted(os.listdir(table_dir)):
            d = os.path.join(table_dir, name)
            if os.path.isdir(d) and os.path.exists(
                    os.path.join(d, "metadata.json")):
                out.append(self.add_segment_dir(d))
        return out

    def remove_segment(self, name: str) -> None:
        with self._lock:
            segs = dict(self._segments)
            seg = segs.pop(name, None)
            self._segments = segs
        if seg is not None and hasattr(seg, "evict_device"):
            # release the device residency NOW (padded columns + stacks
            # + cubes) instead of waiting for GC/LRU: a dropped segment
            # must also leave the device-memory registry, or the
            # /debug/memory live-byte gauges would count dead buffers
            # forever (in-flight queries keep their own array refs —
            # clearing the cache never invalidates them)
            seg.evict_device()
        if seg is not None and getattr(seg, "dir", None):
            # drop any pinned v3 packed-file mmap so unlinked segment
            # files release their disk blocks (segdir LRU backstops this)
            from ..segment import segdir
            segdir.invalidate(seg.dir)

    def replace_segment(self, segment: ImmutableSegment) -> None:
        self.add_segment(segment)  # atomic swap by name

    def reload(self, table_config=None) -> Dict[str, List[str]]:
        """Reconcile every hosted segment's secondary indexes with the
        table config and swap in freshly loaded segments (the reload REST
        operation: segment/local loader/ IndexHandlers + reload message).
        Returns the union of per-segment {'added', 'removed'} changes."""
        from ..segment.loader import reconcile_indexes
        cfg = table_config or self.table_config
        if cfg is None:
            raise ValueError("reload needs a TableConfig")
        self.table_config = cfg
        changes: Dict[str, List[str]] = {"added": [], "removed": []}
        with self._reload_lock:  # one reconcile per table at a time
            for seg in self.acquire_segments():
                seg_dir = getattr(seg, "dir", None)
                if seg_dir is None:
                    continue  # consuming segments: no on-disk indexes yet
                # in-flight queries may hold the OLD segment object and
                # lazily open index files on first use; warming its
                # readers now means it never touches a file this reload
                # is about to delete
                for col, m in seg.columns.items():
                    for kind in list(getattr(m, "indexes", {}) or {}):
                        try:
                            seg.index_reader(col, kind)
                        except Exception:
                            pass
                delta = reconcile_indexes(seg_dir, cfg)
                if delta["added"] or delta["removed"]:
                    seg.evict_device()
                    self.replace_segment(ImmutableSegment.load(seg_dir))
                    changes["added"].extend(delta["added"])
                    changes["removed"].extend(delta["removed"])
        return changes

    def acquire_segments(self) -> List[ImmutableSegment]:
        return list(self._segments.values())

    @property
    def schema(self):
        """Table schema: the declared one if set (realtime managers set it
        at construction), else derived from any loaded segment."""
        if self._schema is not None:
            return self._schema
        for s in self._segments.values():
            return s.schema
        return None

    @schema.setter
    def schema(self, value) -> None:
        self._schema = value

    @property
    def num_segments(self) -> int:
        return len(self._segments)

    @property
    def total_docs(self) -> int:
        return sum(s.n_docs for s in self._segments.values())
