"""A server's id space of a dictionary column over the segments it holds.

Reference parity: Pinot's NoDictionaryMultiColumnGroupKeyGenerator keys
a group-by whose id space is too large for a dense array by the values
of its keys, in a map. A segment's dictionary is local to it, so two
segments give one value different ids and a device combine of their
groups cannot run on ids. Here the server merges its segments' sorted
dictionaries into one sorted dictionary a column, and keeps for every
segment an int32 remap from its local id to the server id. A program
that gathers its keys through the remaps groups rows of every segment
by value, in one id space (ops/kernels.build_topgroups_kernel).

A ``ServerIdSpace`` belongs to one set of segments: the table data
manager makes a new one whenever a segment is added or removed, and a
query that planned against the old one keeps it. A column is merged on
first use (phase ``server_ids_build``), once per space; its remaps reach
the device on first use too, and stay there with the space.
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional

import numpy as np

from ..spi.schema import DataType
from ..utils import phases as ph
from ..utils.metrics import global_metrics
from ..utils.spans import phase

# column types whose dictionaries merge by value (numeric, or strings
# compared as numpy unicode, in the order the segment dictionaries keep)
_MERGEABLE = (DataType.INT, DataType.LONG, DataType.FLOAT, DataType.DOUBLE,
              DataType.STRING)


class ServerColumn:
    """One column's server dictionary and every segment's remap."""

    def __init__(self, values: np.ndarray, remaps: Dict[int, np.ndarray]):
        self.values = values                  # sorted, distinct
        self.remaps = remaps                  # segment uid -> (local card,)
        self._device: Dict[int, object] = {}
        self._lock = threading.Lock()

    @property
    def cardinality(self) -> int:
        return len(self.values)

    def device_remap(self, seg):
        """The segment's remap as an int32 device array (uploaded once)."""
        arr = self._device.get(seg.uid)
        if arr is None:
            import jax
            with self._lock:
                arr = self._device.get(seg.uid)
                if arr is None:
                    arr = jax.device_put(self.remaps[seg.uid])
                    self._device[seg.uid] = arr
        return arr

    def decode(self, ids: np.ndarray) -> list:
        """Server ids -> the Python values every other route gives."""
        return self.values[ids].tolist()


class ServerIdSpace:
    """The id spaces of one set of segments, a column at a time."""

    def __init__(self, segments: List):
        self._segments = list(segments)
        self._columns: Dict[str, Optional[ServerColumn]] = {}
        self._lock = threading.Lock()

    def column(self, name: str) -> Optional[ServerColumn]:
        """The merged column, or None where some segment keeps ``name``
        without a single-value dictionary of a mergeable type."""
        if name in self._columns:
            return self._columns[name]
        with self._lock:
            if name not in self._columns:
                self._columns[name] = self._build(name)
        return self._columns[name]

    def _build(self, name: str) -> Optional[ServerColumn]:
        dicts = []
        for seg in self._segments:
            m = seg.columns.get(name)
            if m is None or not m.has_dict or not m.single_value \
                    or m.data_type not in _MERGEABLE:
                return None
            dicts.append(seg.dictionary(name))
        with phase(ph.SERVER_IDS_BUILD, segments=len(dicts)):
            # a string dictionary is a list: as numpy unicode its sort,
            # unique and searchsorted stay in C
            local = [np.asarray(d.values, dtype=str if isinstance(
                d.values, list) else None) for d in dicts]
            values = np.unique(np.concatenate(local)) if local \
                else np.empty(0)
            remaps = {seg.uid: np.searchsorted(values, vals).astype(np.int32)
                      for seg, vals in zip(self._segments, local)}
        global_metrics.count("server_ids_builds")
        return ServerColumn(values, remaps)
