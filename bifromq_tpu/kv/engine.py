"""KV engine SPI + in-memory engine (≈ base-kv-local-engine-spi / -memory).

Reference shape: ``IKVEngine`` owns named ``IKVSpace``s (one per range;
column-family-per-space in the RocksDB engine), each with point reads, range
iteration over byte-ordered keys, batched writes, metadata, and either
checkpoints (ICPableKVSpace) or WAL fsync (IWALableKVSpace) — see
base-kv/base-kv-local-engine-spi .../localengine/IKVEngine.java, IKVSpace.java,
ICPableKVSpace.java.

The in-memory engine (≈ localengine/memory/InMemKVEngine.java) is the
default for tests and the WAL engine; a native C++ engine can plug in behind
the same SPI.
"""

from __future__ import annotations

import bisect
import threading
from typing import Dict, Iterable, Iterator, List, Optional, Tuple

from .. import trace


class KVWriteBatch:
    """Atomic multi-op write (≈ IKVSpaceWriter)."""

    def __init__(self, space: "IKVSpace") -> None:
        self._space = space
        self._ops: List[Tuple[str, bytes, Optional[bytes]]] = []

    def put(self, key: bytes, value: bytes) -> "KVWriteBatch":
        self._ops.append(("put", key, value))
        return self

    def delete(self, key: bytes) -> "KVWriteBatch":
        self._ops.append(("del", key, None))
        return self

    def delete_range(self, start: bytes, end: bytes) -> "KVWriteBatch":
        self._ops.append(("del_range", start, end))
        return self

    def done(self) -> None:
        self._space._apply(self._ops)
        self._ops = []


class IKVSpace:
    """One named keyspace (≈ IKVSpace): byte-ordered, range-iterable."""

    name: str

    def get(self, key: bytes) -> Optional[bytes]:
        raise NotImplementedError

    def exists(self, key: bytes) -> bool:
        return self.get(key) is not None

    def iterate(self, start: Optional[bytes] = None,
                end: Optional[bytes] = None,
                reverse: bool = False) -> Iterator[Tuple[bytes, bytes]]:
        """Yield (key, value) with start <= key < end in byte order."""
        raise NotImplementedError

    def writer(self) -> KVWriteBatch:
        return KVWriteBatch(self)

    def size(self, start: Optional[bytes] = None,
             end: Optional[bytes] = None) -> int:
        """Approximate byte size of the range (used by split hinters)."""
        raise NotImplementedError

    def __len__(self) -> int:
        """Number of keys, O(1)."""
        raise NotImplementedError

    def checkpoint(self) -> "IKVSpaceCheckpoint":
        raise NotImplementedError

    def destroy(self) -> None:
        raise NotImplementedError

    # metadata (≈ IKVSpace.metadata(): small control records, e.g. range
    # boundary + raft state, kept separate from data keys)
    def get_metadata(self, key: bytes) -> Optional[bytes]:
        raise NotImplementedError

    def put_metadata(self, key: bytes, value: bytes) -> None:
        raise NotImplementedError

    def _apply(self, ops) -> None:
        raise NotImplementedError


class IKVSpaceCheckpoint:
    """Read-only snapshot of a space (≈ IKVSpaceCheckpoint / RocksDB ckpt)."""

    def iterate(self, start: Optional[bytes] = None,
                end: Optional[bytes] = None) -> Iterator[Tuple[bytes, bytes]]:
        raise NotImplementedError

    def get(self, key: bytes) -> Optional[bytes]:
        raise NotImplementedError

    def close(self) -> None:
        pass


class IKVEngine:
    """Engine = a collection of named spaces (≈ IKVEngine)."""

    def create_space(self, name: str) -> IKVSpace:
        raise NotImplementedError

    def get_space(self, name: str) -> Optional[IKVSpace]:
        raise NotImplementedError

    def spaces(self) -> Dict[str, IKVSpace]:
        raise NotImplementedError

    def close(self) -> None:
        pass


# --------------------------- in-memory engine -------------------------------

class _SortedBytesMap:
    """Sorted byte-key map: dict + a key list sorted on demand.

    New keys are appended to a pending tail in O(1) and merged into the
    sorted list by the next ordered read — an ``insort`` per put is an
    O(n) memmove each, which made a 1M-route bulk load quadratic, and a
    re-sort per merge is O(n) compares, which made every live UNSUBSCRIBE
    a 1M-key sort: ``_sorted`` takes whichever the tail's length makes
    cheaper. Reads by key are O(1); range scans are O(log n + k) once
    merged.
    """

    def __init__(self) -> None:
        self._keys: List[bytes] = []
        self._pending: List[bytes] = []
        self._map: Dict[bytes, bytes] = {}

    # a pending tail up to this long is placed key by key: a binary search
    # and one pointer memmove each (0.3 ms at 1M keys), where appending and
    # re-sorting walks the whole list whatever the tail (40-110 ms at 1M
    # keys, once a live UNSUBSCRIBE). The two meet near 300 keys at any n.
    INSORT_MAX = 64

    def _sorted(self) -> List[bytes]:
        if self._pending:
            with trace.span("kv.resort"):
                if len(self._pending) <= self.INSORT_MAX:
                    for key in self._pending:
                        bisect.insort(self._keys, key)
                else:
                    self._keys.extend(self._pending)
                    self._keys.sort()
                self._pending.clear()
        return self._keys

    def put(self, key: bytes, value: bytes) -> None:
        if key not in self._map:
            self._pending.append(key)
        self._map[key] = value

    def delete(self, key: bytes) -> None:
        if key in self._map:
            del self._map[key]
            keys = self._sorted()
            i = bisect.bisect_left(keys, key)
            del keys[i]

    def delete_range(self, start: bytes, end: bytes) -> None:
        keys = self._sorted()
        lo = bisect.bisect_left(keys, start)
        hi = bisect.bisect_left(keys, end)
        for k in keys[lo:hi]:
            del self._map[k]
        del keys[lo:hi]

    def get(self, key: bytes) -> Optional[bytes]:
        return self._map.get(key)

    def keys_in(self, start: Optional[bytes], end: Optional[bytes],
                reverse: bool = False) -> List[bytes]:
        """A copy of the keys in [start, end), in iteration order."""
        all_keys = self._sorted()
        lo = 0 if start is None else bisect.bisect_left(all_keys, start)
        hi = len(all_keys) if end is None else bisect.bisect_left(
            all_keys, end)
        return all_keys[lo:hi][::-1] if reverse else all_keys[lo:hi]

    def scan(self, start: Optional[bytes], end: Optional[bytes],
             reverse: bool = False) -> Iterator[Tuple[bytes, bytes]]:
        for k in self.keys_in(start, end, reverse):
            yield k, self._map[k]

    def copy(self) -> "_SortedBytesMap":
        c = _SortedBytesMap()
        c._keys = list(self._sorted())
        c._map = dict(self._map)
        return c

    def __len__(self) -> int:
        return len(self._map)


class InMemKVSpace(IKVSpace):
    def __init__(self, engine: "InMemKVEngine", name: str) -> None:
        self.name = name
        self._engine = engine
        self._data = _SortedBytesMap()
        self._meta: Dict[bytes, bytes] = {}
        self._lock = threading.RLock()

    def get(self, key: bytes) -> Optional[bytes]:
        with self._lock:
            return self._data.get(key)

    def iterate(self, start: Optional[bytes] = None,
                end: Optional[bytes] = None,
                reverse: bool = False) -> Iterator[Tuple[bytes, bytes]]:
        # the KEY range is snapshotted under the lock (a pointer copy);
        # values are read as the consumer advances, so a consumer that
        # takes only the first pair — the dist coproc's endpoint probes on
        # the match path — pays O(log n), not a copy of the whole range.
        # Keys deleted meanwhile are skipped.
        with self._lock:
            keys = self._data.keys_in(start, end, reverse)
        for k in keys:
            v = self._data.get(k)
            if v is not None:
                yield k, v

    def size(self, start: Optional[bytes] = None,
             end: Optional[bytes] = None) -> int:
        with self._lock:
            return sum(len(k) + len(v)
                       for k, v in self._data.scan(start, end))

    def checkpoint(self) -> IKVSpaceCheckpoint:
        with self._lock:
            return _InMemCheckpoint(self._data.copy())

    def destroy(self) -> None:
        self._engine._drop(self.name)

    def get_metadata(self, key: bytes) -> Optional[bytes]:
        with self._lock:
            return self._meta.get(key)

    def put_metadata(self, key: bytes, value: bytes) -> None:
        with self._lock:
            self._meta[key] = value

    def _apply(self, ops) -> None:
        with self._lock:
            for op, a, b in ops:
                if op == "put":
                    self._data.put(a, b)
                elif op == "del":
                    self._data.delete(a)
                elif op == "del_range":
                    self._data.delete_range(a, b)

    def __len__(self) -> int:
        with self._lock:
            return len(self._data)


class _InMemCheckpoint(IKVSpaceCheckpoint):
    def __init__(self, snapshot: _SortedBytesMap) -> None:
        self._snap = snapshot

    def iterate(self, start: Optional[bytes] = None,
                end: Optional[bytes] = None) -> Iterator[Tuple[bytes, bytes]]:
        yield from self._snap.scan(start, end)

    def get(self, key: bytes) -> Optional[bytes]:
        return self._snap.get(key)


class InMemKVEngine(IKVEngine):
    def __init__(self) -> None:
        self._spaces: Dict[str, InMemKVSpace] = {}
        self._lock = threading.Lock()

    def create_space(self, name: str) -> IKVSpace:
        with self._lock:
            sp = self._spaces.get(name)
            if sp is None:
                sp = InMemKVSpace(self, name)
                self._spaces[name] = sp
            return sp

    def get_space(self, name: str) -> Optional[IKVSpace]:
        return self._spaces.get(name)

    def spaces(self) -> Dict[str, IKVSpace]:
        return dict(self._spaces)

    def _drop(self, name: str) -> None:
        with self._lock:
            self._spaces.pop(name, None)
