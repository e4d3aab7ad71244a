"""BiMap — bidirectional entity-id ↔ dense-index mapping.

Reference: data/.../data/storage/BiMap.scala (stringInt/stringLong helpers
used by every recommendation template to map entity ids onto matrix rows).
The TPU build leans on it even harder: dense int32 indices are what XLA
wants; strings stay on the host.
"""

from __future__ import annotations

import operator
import threading
from typing import Hashable, Iterable, Mapping, Optional, Sequence, Union

import numpy as np

from ...native import IdTable

#: the one key of an array-backed map's persisted form (as
#: ``__identity_n__`` is IdentityBiMap's): its value is (blob, offsets)
_ID_TABLE_KEY = "__id_table__"

# guards the one-time build of an array-backed map's forward dict; one
# lock for every map (a module global pickles with no map)
_FORWARD_LOCK = threading.Lock()


class BiMap:
    """Immutable bidirectional map key → value (both unique).

    Two backings, chosen by what the map is built from. A mapping: a
    dict and its inverse, built at once. An :class:`IdTable` whose k-th
    string is the key of value k (the event store's read hands its id
    tables over so): the inverse side, ``len`` and ``keys()`` read the
    table and make a string when asked for that id; the forward dict is
    built once, at the first forward lookup (at deploy that is the
    model's ``warm_up``); no inverse dict is ever built."""

    #: None on the mapping backing (and on a map unpickled from an
    #: artifact older than the second backing)
    _table: Optional[IdTable] = None

    def __init__(self, forward: Union[Mapping[Hashable, int], IdTable]):
        if isinstance(forward, IdTable):
            self._table = forward
            self._fwd: Optional[dict] = None
            return
        self._fwd = dict(forward)
        self._inv = {v: k for k, v in self._fwd.items()}
        if len(self._inv) != len(self._fwd):
            raise ValueError("BiMap values must be unique")

    def _forward(self) -> dict:
        """The forward dict, built from the table at its first use."""
        fwd = self._fwd
        if fwd is None:
            with _FORWARD_LOCK:
                fwd = self._fwd
                if fwd is None:
                    keys = self._table.tolist()
                    fwd = dict(zip(keys, range(len(keys))))
                    if len(fwd) != len(keys):
                        raise ValueError("BiMap keys must be unique")
                    self._fwd = fwd
        return fwd

    def __getstate__(self) -> dict:
        # an array-backed map pickles as its table, never as a dict it
        # may have built; the mapping backing pickles as it always has
        state = dict(self.__dict__)
        if self._table is not None:
            state["_fwd"] = None
        return state

    @staticmethod
    def string_int(keys: Iterable[str]) -> "BiMap":
        """Assign consecutive int indices to (deduped) keys in first-seen
        order (reference: BiMap.stringInt)."""
        fwd: dict[str, int] = {}
        for k in keys:
            if k not in fwd:
                fwd[k] = len(fwd)
        return BiMap(fwd)

    def __call__(self, key: Hashable) -> int:
        return self._forward()[key]

    def get(self, key: Hashable, default: Optional[int] = None) -> Optional[int]:
        return self._forward().get(key, default)

    def inverse(self, value: int) -> Hashable:
        if self._table is None:
            return self._inv[value]
        try:
            v = operator.index(value)
        except TypeError:
            raise KeyError(value) from None
        if not 0 <= v < len(self._table):
            raise KeyError(value)
        return self._table[v]

    def inverse_get(self, value: int, default=None):
        if self._table is None:
            return self._inv.get(value, default)
        try:
            return self.inverse(value)
        except KeyError:
            return default

    def contains(self, key: Hashable) -> bool:
        return key in self._forward()

    __contains__ = contains

    def __len__(self) -> int:
        if self._table is not None:
            return len(self._table)
        return len(self._fwd)

    def keys(self):
        if self._fwd is None:
            return _TableKeys(self)
        return self._fwd.keys()

    def to_dict(self) -> dict:
        return dict(self._forward())

    # -- persistence (identity-aware) -------------------------------------
    def to_persisted(self):
        """Model-blob form. IdentityBiMap overrides with a compact
        marker so persisting a 36M-item identity mapping doesn't
        materialize 36M dict entries; an array-backed map hands over its
        table under a marker key, so pickle writes two buffers and no
        entry."""
        if self._table is not None:
            return {_ID_TABLE_KEY: (self._table.blob, self._table.offs)}
        return self.to_dict()

    @staticmethod
    def from_persisted(obj) -> "BiMap":
        """Inverse of to_persisted: detects the identity and the id-table
        markers; a plain dict is the form of every older artifact."""
        if isinstance(obj, Mapping) and len(obj) == 1:
            if "__identity_n__" in obj:
                return IdentityBiMap(obj["__identity_n__"])
            table = obj.get(_ID_TABLE_KEY)
            if isinstance(table, tuple):
                return BiMap(IdTable(*table))
        if isinstance(obj, BiMap):
            return obj
        return BiMap(obj)

    def map_array(self, keys: Sequence[Hashable]) -> np.ndarray:
        """Vectorized lookup → int32 numpy array (device-ready)."""
        fwd = self._forward()
        return np.fromiter((fwd[k] for k in keys), dtype=np.int32, count=len(keys))

    def inverse_array(self, values: Sequence[int]) -> list:
        if self._table is None:
            return [self._inv[int(v)] for v in values]
        values = np.asarray(values, np.int64).reshape(-1)
        n = len(self._table)
        bad = values[(values < 0) | (values >= n)]
        if bad.size:
            raise KeyError(int(bad[0]))
        return self._table.strings(values)


class IdentityBiMap(BiMap):
    """``str(i) ↔ i`` over [0, n) WITHOUT materializing n entries.

    ALX-scale catalogs (tens of millions of items served sharded —
    ops/sharded_topk.py) only ever need the arithmetic mapping; a dict
    BiMap at 36M items costs multiple GiB of host RAM and minutes of
    construction for information that is pure ``int()``/``str()``."""

    def __init__(self, n: int):
        self._n = int(n)

    def __call__(self, key: Hashable) -> int:
        v = self.get(key)
        if v is None:
            raise KeyError(key)
        return v

    def get(self, key: Hashable, default: Optional[int] = None) -> Optional[int]:
        # STRICT str keys: a dict BiMap keyed by str(i) rejects the int 4
        # even though str(4) would canonicalize — query JSON sends both,
        # and the two BiMap kinds must answer identically
        if not isinstance(key, str):
            return default
        try:
            v = int(key, 10)
        except ValueError:
            return default
        # reject non-canonical spellings ("07", "+3", " 5") likewise
        if 0 <= v < self._n and key == str(v):
            return v
        return default

    def inverse(self, value: int) -> str:
        v = int(value)
        if not 0 <= v < self._n:
            raise KeyError(value)
        return str(v)

    def inverse_get(self, value: int, default=None):
        try:
            return self.inverse(value)
        except (KeyError, TypeError, ValueError):
            return default

    def contains(self, key: Hashable) -> bool:
        return self.get(key) is not None

    __contains__ = contains

    def __len__(self) -> int:
        return self._n

    def keys(self):
        return _IdentityKeys(self._n)

    def to_dict(self) -> dict:
        return {str(j): j for j in range(self._n)}

    def to_persisted(self):
        return {"__identity_n__": self._n}

    def map_array(self, keys: Sequence[Hashable]) -> np.ndarray:
        return np.fromiter((self(k) for k in keys), dtype=np.int32,
                           count=len(keys))

    def inverse_array(self, values: Sequence[int]) -> list:
        return [self.inverse(v) for v in values]


def extend_bimap(bm: BiMap, keys: Iterable[str]):
    """A NEW BiMap with ``keys`` appended after the existing indices
    (first-seen order), for the streaming fold-in path (new users/items
    arriving after training get matrix rows past the trained ones).
    ``bm`` is never mutated — BiMaps are immutable by contract.

    Returns ``(bimap, appended)``. An :class:`IdentityBiMap` extends
    WITHOUT materializing (only when the new keys are exactly the next
    consecutive ``str(n)..`` ids — anything else would force a
    multi-GB dict at ALX scale, so those keys are refused: callers
    skip the events and log)."""
    new = []
    seen = set()
    for k in keys:
        if k not in seen and k not in bm:
            seen.add(k)
            new.append(k)
    if not new:
        return bm, []
    if isinstance(bm, IdentityBiMap):
        n = len(bm)
        if set(new) == {str(n + j) for j in range(len(new))}:
            return IdentityBiMap(n + len(new)), new
        return bm, []
    fwd = bm.to_dict()
    for k in new:
        fwd[k] = len(fwd)
    return BiMap(fwd), new


class _IdentityKeys:
    """Reusable view over str(0..n) — matches dict_keys' re-iterability
    and len() (a one-shot generator would silently diverge)."""

    def __init__(self, n: int):
        self._n = n

    def __iter__(self):
        return (str(j) for j in range(self._n))

    def __len__(self) -> int:
        return self._n

    def __contains__(self, key) -> bool:
        return IdentityBiMap(self._n).get(key) is not None


class _TableKeys:
    """Reusable view over an array-backed map's keys in value order:
    re-iterable and sized, as dict_keys is."""

    def __init__(self, bimap: BiMap):
        self._bimap = bimap

    def __iter__(self):
        table = self._bimap._table
        return (table[k] for k in range(len(table)))

    def __len__(self) -> int:
        return len(self._bimap)

    def __contains__(self, key) -> bool:
        return key in self._bimap
