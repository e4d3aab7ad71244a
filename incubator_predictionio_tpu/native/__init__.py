"""Native event-log codec bindings (ctypes over native/src/event_codec.cc).

The C++ library is the scan path of the JSONL event store — the role the
HBase client + TableInputFormat scan play in the reference (storage/hbase/
.../HBPEvents.scala). ``parse_events_jsonl`` decodes a JSONL buffer into
``ColumnarEvents``: interned id codes + timestamps + ratings as numpy
arrays, the exact host-side layout the input pipeline uploads to device.
A large buffer is cut at newlines and decoded as pieces on native threads
(ctypes releases the GIL for the call), merged to the one pass's codes.

Build strategy: the .so is compiled lazily on first use (one translation
unit, ~1s with g++ -O3 -pthread) into ``_lib/`` next to this file, keyed by the ABI
version the library exports and by a hash of the source it was built
from; `make -C native` does the same for packaging. When no C++ toolchain is available ``parse_events_jsonl``
raises ``NativeUnavailable`` and callers fall back to the pure-Python
scan — behavior is identical, only slower (tests assert equality).
"""

from __future__ import annotations

import ctypes
import hashlib
import json
import os
import subprocess
import threading
from dataclasses import dataclass
from typing import Optional

import numpy as np

from ..common import telemetry

_EXPECTED_VERSION = 20

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_lib_error: Optional[str] = None


class NativeUnavailable(RuntimeError):
    pass


class EventParseError(ValueError):
    pass


def _src_path() -> str:
    repo_root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    return os.path.join(repo_root, "native", "src", "event_codec.cc")


def _src_digest() -> str:
    """Short content hash of the codec source ("" when the source is not
    there to hash)."""
    try:
        with open(_src_path(), "rb") as f:
            return hashlib.sha256(f.read()).hexdigest()[:12]
    except OSError:
        return ""


def _lib_path() -> str:
    # ABI version in the filename: glibc dlopen dedups by pathname, so a
    # same-path rebuild inside a live process would silently resolve to
    # the stale mapped library (its symbols, not the new ones). The
    # source hash beside it: _lib/ is git-ignored and survives checkouts
    # and tree copies, so an edited source with an unchanged ABI number
    # must not find — and run — the binary of the old source.
    digest = _src_digest()
    return os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "_lib",
        f"libpioevent.v{_EXPECTED_VERSION}{'.' + digest if digest else ''}"
        ".so")


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    lib.pio_codec_version.restype = ctypes.c_int32
    lib.pio_parse_events_jsonl.restype = ctypes.c_void_p
    lib.pio_parse_events_jsonl.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_int32, ctypes.c_char_p,
        ctypes.c_int64,
    ]
    lib.pio_col_count.restype = ctypes.c_int64
    lib.pio_col_count.argtypes = [ctypes.c_void_p]
    lib.pio_parse_stats.restype = None
    lib.pio_parse_stats.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64)]
    lib.pio_threads_started.restype = ctypes.c_int64
    lib.pio_threads_started.argtypes = []
    lib.pio_export_columns.restype = None
    lib.pio_export_columns.argtypes = (
        [ctypes.c_void_p] + [ctypes.POINTER(ctypes.c_int32)] * 6
        + [ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_float),
           ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64)])
    lib.pio_table_size.restype = ctypes.c_int32
    lib.pio_table_size.argtypes = [ctypes.c_void_p, ctypes.c_int32]
    lib.pio_table_blob.restype = ctypes.POINTER(ctypes.c_char)
    lib.pio_table_blob.argtypes = [
        ctypes.c_void_p, ctypes.c_int32, ctypes.POINTER(ctypes.c_int64),
    ]
    lib.pio_table_offsets.restype = ctypes.POINTER(ctypes.c_int64)
    lib.pio_table_offsets.argtypes = [ctypes.c_void_p, ctypes.c_int32]
    lib.pio_tombstone_count.restype = ctypes.c_int64
    lib.pio_tombstone_count.argtypes = [ctypes.c_void_p]
    lib.pio_tombstone_pos.restype = ctypes.POINTER(ctypes.c_int64)
    lib.pio_tombstone_pos.argtypes = [ctypes.c_void_p]
    lib.pio_tombstone_get.restype = ctypes.POINTER(ctypes.c_char)
    lib.pio_tombstone_get.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.POINTER(ctypes.c_int32),
    ]
    lib.pio_free.restype = None
    lib.pio_free.argtypes = [ctypes.c_void_p]
    lib.pio_ingest_batch.restype = ctypes.c_void_p
    lib.pio_ingest_batch.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_char_p, ctypes.c_int64,
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int64,
    ]
    lib.pio_ingest_count.restype = ctypes.c_int64
    lib.pio_ingest_count.argtypes = [ctypes.c_void_p]
    lib.pio_ingest_all_ok.restype = ctypes.c_int32
    lib.pio_ingest_all_ok.argtypes = [ctypes.c_void_p]
    lib.pio_ingest_lines.restype = ctypes.POINTER(ctypes.c_char)
    lib.pio_ingest_lines.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64)]
    lib.pio_ingest_free.restype = None
    lib.pio_ingest_free.argtypes = [ctypes.c_void_p]
    lib.pio_cco_partition.restype = ctypes.c_void_p
    lib.pio_cco_partition.argtypes = [
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
        ctypes.c_int64, ctypes.POINTER(ctypes.c_int32), ctypes.c_int64,
        ctypes.c_int32, ctypes.c_int64, ctypes.c_int64, ctypes.c_int32,
        ctypes.c_int64,
    ]
    lib.pio_ccop_dim.restype = ctypes.c_int64
    lib.pio_ccop_dim.argtypes = [ctypes.c_void_p, ctypes.c_int32]
    lib.pio_ccop_slab.restype = ctypes.POINTER(ctypes.c_uint16)
    lib.pio_ccop_slab.argtypes = [ctypes.c_void_p, ctypes.c_int32]
    lib.pio_ccop_item_counts.restype = ctypes.POINTER(ctypes.c_int64)
    lib.pio_ccop_item_counts.argtypes = [ctypes.c_void_p]
    lib.pio_ccop_free.restype = None
    lib.pio_ccop_free.argtypes = [ctypes.c_void_p]
    lib.pio_pair_dedupe.restype = ctypes.c_void_p
    lib.pio_pair_dedupe.argtypes = [
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
    ]
    lib.pio_pdd_count.restype = ctypes.c_int64
    lib.pio_pdd_count.argtypes = [ctypes.c_void_p]
    for name in ("pio_pdd_users", "pio_pdd_items"):
        fn = getattr(lib, name)
        fn.restype = ctypes.POINTER(ctypes.c_int32)
        fn.argtypes = [ctypes.c_void_p]
    lib.pio_pdd_per_user.restype = ctypes.POINTER(ctypes.c_int64)
    lib.pio_pdd_per_user.argtypes = [ctypes.c_void_p]
    lib.pio_pdd_free.restype = None
    lib.pio_pdd_free.argtypes = [ctypes.c_void_p]
    lib.pio_fill_entries.restype = ctypes.c_int32
    lib.pio_fill_entries.argtypes = [
        ctypes.POINTER(ctypes.c_int64),   # row
        ctypes.POINTER(ctypes.c_int64),   # col
        ctypes.POINTER(ctypes.c_float),   # val
        ctypes.c_int64,                   # nnz
        ctypes.POINTER(ctypes.c_int64),   # col_slot_map
        ctypes.c_int64,                   # n_cols
        ctypes.POINTER(ctypes.c_int64),   # prim_base
        ctypes.POINTER(ctypes.c_int64),   # v_base
        ctypes.POINTER(ctypes.c_int64),   # vc_e
        ctypes.POINTER(ctypes.c_int64),   # cursor scratch
        ctypes.c_int64,                   # n_rows
        ctypes.POINTER(ctypes.c_int32),   # flat_cols
        ctypes.POINTER(ctypes.c_float),   # flat_vals
        ctypes.c_int64,                   # total
    ]
    lib.pio_take_strings.restype = ctypes.c_int32
    lib.pio_take_strings.argtypes = [
        ctypes.c_char_p,                  # the table's blob
        ctypes.POINTER(ctypes.c_int64),   # its offsets [size + 1]
        ctypes.c_int64,                   # size
        ctypes.POINTER(ctypes.c_int64),   # codes [n]
        ctypes.c_int64,                   # n
        ctypes.POINTER(ctypes.c_uint8),   # out blob
        ctypes.POINTER(ctypes.c_int64),   # out offsets [n + 1]
    ]
    lib.pio_tfidf_tf.restype = ctypes.c_int32
    lib.pio_tfidf_tf.argtypes = [
        ctypes.c_char_p,                  # concatenated utf-8 docs
        ctypes.POINTER(ctypes.c_int64),   # offsets [n_docs + 1]
        ctypes.c_int64,                   # n_docs
        ctypes.c_int32,                   # n_features
        ctypes.c_int32,                   # ngram
        ctypes.POINTER(ctypes.c_float),   # out [n_docs, n_features]
        ctypes.POINTER(ctypes.c_int64),   # df [n_features] or NULL
    ]
    lib.pio_tfidf_tf_coo.restype = ctypes.c_int64
    lib.pio_tfidf_tf_coo.argtypes = [
        ctypes.c_char_p,                  # concatenated utf-8 docs
        ctypes.POINTER(ctypes.c_int64),   # offsets [n_docs + 1]
        ctypes.c_int64,                   # n_docs
        ctypes.c_int32,                   # n_features
        ctypes.c_int32,                   # ngram
        ctypes.c_int64,                   # cap
        ctypes.POINTER(ctypes.c_int64),   # doc_ptr [n_docs + 1]
        ctypes.POINTER(ctypes.c_int32),   # feat_out [cap]
        ctypes.POINTER(ctypes.c_float),   # cnt_out [cap]
        ctypes.POINTER(ctypes.c_int64),   # df [n_features] or NULL
    ]
    return lib


def _build() -> str:
    out = _lib_path()
    os.makedirs(os.path.dirname(out), exist_ok=True)
    tmp = out + f".tmp{os.getpid()}"
    cmd = ["g++", "-O3", "-std=c++17", "-fPIC", "-Wall", "-pthread",
           "-shared", "-o", tmp, _src_path()]
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
    if proc.returncode != 0:
        raise NativeUnavailable(f"g++ build failed: {proc.stderr[-2000:]}")
    os.replace(tmp, out)
    # drop superseded builds (older ABI versions, older sources)
    import glob

    for stale in glob.glob(os.path.join(os.path.dirname(out), "libpioevent*.so")):
        if stale != out:
            try:
                os.unlink(stale)
            except OSError:
                pass
    return out


def _load() -> ctypes.CDLL:
    global _lib, _lib_error
    from ..common import envknobs

    if envknobs.env_flag("PIO_DISABLE_NATIVE", False):
        # operational kill-switch: force every caller onto the pure-
        # Python fallbacks (e.g. a miscompiling toolchain in the field)
        raise NativeUnavailable("disabled by PIO_DISABLE_NATIVE=1")
    with _lock:
        if _lib is not None:
            return _lib
        if _lib_error is not None:
            raise NativeUnavailable(_lib_error)
        try:
            path = _lib_path()
            lib = None
            if os.path.exists(path):
                try:
                    candidate = _bind(ctypes.CDLL(path))
                    if candidate.pio_codec_version() == _EXPECTED_VERSION:
                        lib = candidate
                except (OSError, AttributeError):
                    pass  # stale/corrupt cache → rebuild below
            if lib is None:
                lib = _bind(ctypes.CDLL(_build()))
                if lib.pio_codec_version() != _EXPECTED_VERSION:
                    raise NativeUnavailable(
                        "built library ABI version mismatch — source/wrapper skew"
                    )
            _lib = lib
            return _lib
        except NativeUnavailable as e:
            _lib_error = str(e)
            raise
        except Exception as e:  # toolchain/loader failures degrade cleanly
            _lib_error = f"native codec unavailable: {e}"
            raise NativeUnavailable(_lib_error) from e


def loaded() -> Optional[ctypes.CDLL]:
    """The already-loaded library, or None — NEVER loads or builds.
    Hot paths that may run ON an event loop (the ingest fast paths) use
    this so a cold cache can't turn into a g++ build stalling every
    connection; a sync context (server construction) pays the build via
    :func:`available`. Honours the PIO_DISABLE_NATIVE kill-switch
    per-call exactly like :func:`_load` — the operational escape hatch
    must cover the hot path too, resident library or not."""
    from ..common import envknobs

    if envknobs.env_flag("PIO_DISABLE_NATIVE", False):
        return None
    return _lib


def available() -> bool:
    try:
        _load()
        return True
    except NativeUnavailable:
        return False


_M_ID_STRINGS = telemetry.registry().counter(
    "pio_store_id_strings_total",
    "Python strings decoded out of the id tables of a log scan's columns, "
    "by table (event, entityType, entityId, targetEntityType, "
    "targetEntityId, eventId); a table that is a list already decodes "
    "nothing", ("table",))

_TABLE_NAMES = ("event", "entityType", "entityId", "targetEntityType",
                "targetEntityId", "eventId")


class IdTable:
    """An id table as the codec hands it over: the strings' utf-8 bytes
    end to end in ``blob`` and ``size + 1`` int64 end offsets in
    ``offs``. A string is made when somebody asks for that id; the
    object pickles as its two buffers."""

    __slots__ = ("blob", "offs")

    def __init__(self, blob: bytes, offs: np.ndarray) -> None:
        self.blob = blob
        self.offs = offs

    def __reduce__(self):
        return IdTable, (self.blob, self.offs)

    def __len__(self) -> int:
        return len(self.offs) - 1

    def __getitem__(self, k: int) -> str:
        return self.blob[self.offs[k]:self.offs[k + 1]].decode("utf-8")

    def strings(self, codes) -> list[str]:
        """The strings of the given codes, in their order."""
        codes = np.asarray(codes, np.int64)
        blob, offs = self.blob, self.offs
        return [blob[s:e].decode("utf-8") for s, e in zip(
            offs[codes].tolist(), offs[codes + 1].tolist())]

    def tolist(self) -> list[str]:
        """Every string, in code order."""
        blob, ends = self.blob, self.offs.tolist()
        text = blob.decode("utf-8")
        if len(text) == len(blob):  # pure ASCII: str slicing == byte slicing
            return [text[s:e] for s, e in zip(ends, ends[1:])]
        return [blob[s:e].decode("utf-8") for s, e in zip(ends, ends[1:])]

    def take(self, codes) -> "IdTable":
        """The table of the given codes, in their order (a copy of byte
        ranges in the codec, which made this table)."""
        lib = _load()
        codes = np.ascontiguousarray(codes, np.int64)
        offs = np.zeros(len(codes) + 1, np.int64)
        np.cumsum(self.offs[codes + 1] - self.offs[codes], out=offs[1:])
        out = np.empty(int(offs[-1]), np.uint8)
        p64 = ctypes.POINTER(ctypes.c_int64)
        rc = lib.pio_take_strings(
            self.blob, self.offs.ctypes.data_as(p64), len(self),
            codes.ctypes.data_as(p64), len(codes),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            offs.ctypes.data_as(p64))
        if rc != 0:
            raise ValueError(f"take: code outside the table (error {rc})")
        return IdTable(out.tobytes(), offs)


@dataclass
class ColumnarEvents:
    """Interned columnar view of an event log scan.

    Code -1 in ``tetype``/``teid``/``event_id`` = field absent;
    ``time_us`` INT64_MIN = absent; ``rating`` NaN = key absent, -inf =
    key present but not coercible to a finite number (the two fill
    differently in find_ratings). ``props`` and ``span`` are [start, end)
    byte offsets into ``raw`` (-1 = absent) for lazy per-event reparse of
    the full JSON. ``tombstone_pos[i]`` = how many event records precede
    tombstone i (deletes are positional: later re-inserts are live).

    A table is an :class:`IdTable` (the codec's) or a list of strings
    (the Python parser's, a snapshot's, an extended scan's).
    ``table_size`` and ``strings`` read either form and make no string
    nobody asked for; ``table(which)`` turns the whole table into a
    list once — the eventId table of a big scan is as large as the scan
    itself, and the training fast path never touches it.
    """

    raw: bytes
    event: np.ndarray
    etype: np.ndarray
    eid: np.ndarray
    tetype: np.ndarray
    teid: np.ndarray
    event_id: np.ndarray
    time_us: np.ndarray
    rating: np.ndarray
    props: np.ndarray  # (n, 2) int64
    span: np.ndarray  # (n, 2) int64
    # per table: an IdTable or the already-built list
    _tables: list
    tombstones: list[str]
    tombstone_pos: np.ndarray  # int64, record count before each tombstone
    # how the parse that made these columns ran (``parse_events_jsonl``);
    # None for columns that no parse made (a snapshot, an extended scan)
    parse_stats: Optional[dict] = None

    def __len__(self) -> int:
        return int(self.event.shape[0])

    TABLE_EVENT, TABLE_ETYPE, TABLE_EID = 0, 1, 2
    TABLE_TETYPE, TABLE_TEID, TABLE_EVENT_ID = 3, 4, 5

    def table(self, which: int) -> list[str]:
        t = self._tables[which]
        if isinstance(t, list):
            return t
        out = t.tolist()
        _M_ID_STRINGS.labels(_TABLE_NAMES[which]).inc(len(out))
        self._tables[which] = out
        return out

    def table_size(self, which: int) -> int:
        return len(self._tables[which])

    def strings(self, which: int, codes) -> list[str]:
        """The strings of the given codes of one table, in their order."""
        t = self._tables[which]
        if isinstance(t, list):
            return [t[c] for c in np.asarray(codes).tolist()]
        out = t.strings(codes)
        _M_ID_STRINGS.labels(_TABLE_NAMES[which]).inc(len(out))
        return out

    def take(self, which: int, codes):
        """The ids of the given codes of one table, in their order, in
        the table's own form: an :class:`IdTable` of a table that is
        one, else a list of strings."""
        t = self._tables[which]
        if isinstance(t, IdTable):
            return t.take(codes)
        return self.strings(which, codes)

    @property
    def tables(self) -> list[list[str]]:
        return [self.table(w) for w in range(6)]

    def properties_dict(self, i: int) -> dict:
        s, e = self.props[i]
        if s < 0:
            return {}
        return json.loads(self.raw[s:e])

    def record_dict(self, i: int) -> dict:
        s, e = self.span[i]
        return json.loads(self.raw[s:e])


def _np_copy(ptr, n, dtype):
    if n == 0:
        return np.empty(0, dtype=dtype)
    return np.ctypeslib.as_array(ptr, shape=(n,)).astype(dtype, copy=True)


_PARSE_MODES = ("whole", "split", "fallback")


def parse_events_jsonl(buf: bytes,
                       pieces: Optional[int] = None) -> ColumnarEvents:
    """Parse a JSONL buffer of event objects (native fast path).

    A large buffer is cut at newlines into pieces that are parsed side
    by side on threads and merged to the codes of one pass: every table
    numbers its strings in order of first occurrence in the file, so
    the result is the one pass's, field for field. How many pieces and
    threads comes from the buffer's bytes and the CPUs this process may
    run on (``derive_pieces`` in event_codec.cc); a buffer under the
    floor takes the one pass on the calling thread, and so does one of
    which any piece fails (a record that spans lines, a raw newline in
    a string, a malformed record: the one pass then gives the true
    columns or the true error). ``pieces`` forces a count, for tests.
    ``ColumnarEvents.parse_stats`` says how it ran: ``mode``
    (``whole`` | ``split`` | ``fallback``), ``pieces``, ``threads``,
    ``merge_ms``.

    Raises NativeUnavailable when no toolchain/library, EventParseError on
    malformed input. Pure-Python equivalent: ``parse_events_jsonl_py``.
    """
    lib = _load()
    err = ctypes.create_string_buffer(512)
    handle = lib.pio_parse_events_jsonl(
        buf, len(buf), pieces or 0, err, len(err))
    if not handle:
        raise EventParseError(err.value.decode(errors="replace") or "parse failed")
    try:
        n = lib.pio_col_count(handle)
        raw_stats = (ctypes.c_int64 * 4)()
        lib.pio_parse_stats(handle, raw_stats)
        tables = []
        for which in range(6):
            size = lib.pio_table_size(handle, which)
            if size == 0:
                tables.append(IdTable(b"", np.zeros(1, np.int64)))
                continue
            blob_len = ctypes.c_int64(0)
            blob_ptr = lib.pio_table_blob(handle, which, ctypes.byref(blob_len))
            blob = ctypes.string_at(blob_ptr, blob_len.value)
            offs = _np_copy(lib.pio_table_offsets(handle, which), size + 1, np.int64)
            tables.append(IdTable(blob, offs))
        tombstones = []
        ln = ctypes.c_int32(0)
        n_tomb = lib.pio_tombstone_count(handle)
        for idx in range(n_tomb):
            ptr = lib.pio_tombstone_get(handle, idx, ctypes.byref(ln))
            tombstones.append(ctypes.string_at(ptr, ln.value).decode("utf-8"))
        tombstone_pos = _np_copy(lib.pio_tombstone_pos(handle), n_tomb, np.int64)
        out = {name: np.empty(n, np.int32) for name in (
            "event", "etype", "eid", "tetype", "teid", "event_id")}
        out["time_us"] = np.empty(n, np.int64)
        out["rating"] = np.empty(n, np.float32)
        out["props"] = np.empty((n, 2), np.int64)
        out["span"] = np.empty((n, 2), np.int64)
        # the pieces copy themselves out side by side, codes rewritten
        # to the merged tables on the way
        lib.pio_export_columns(handle, *(
            a.ctypes.data_as(ctypes.POINTER(
                np.ctypeslib.as_ctypes_type(a.dtype)))
            for a in out.values()))
        return ColumnarEvents(
            raw=buf, **out,
            _tables=tables,
            tombstones=tombstones,
            tombstone_pos=tombstone_pos,
            parse_stats={
                "mode": _PARSE_MODES[raw_stats[0]],
                "pieces": int(raw_stats[1]),
                "threads": int(raw_stats[2]),
                "merge_ms": round(raw_stats[3] / 1000.0, 3),
            },
        )
    finally:
        lib.pio_free(handle)


_FILL_ERRORS = {
    -1: "column id outside the counterpart slot map",
    -2: "computed destination outside the flat buffer (inconsistent plan)",
    -3: "row id outside [0, n_rows)",
}


def fill_entries(row: np.ndarray, col: np.ndarray, val, col_slot_map,
                 prim_base: np.ndarray, v_base: np.ndarray,
                 vc_e: np.ndarray, flat_cols: np.ndarray,
                 flat_vals) -> None:
    """Native scatter for ops/rowblocks.fill_buckets (see event_codec.cc).

    Mutates ``flat_cols``/``flat_vals`` in place; within-row entry order
    is the original order, bit-identical to the numpy fallback path.
    ``val``/``flat_vals`` may be None together (binary-ratings mode —
    the value slabs are never built). Raises NativeUnavailable when no
    toolchain, ValueError on the contract violations the library
    range-checks.
    """
    lib = _load()
    n_rows = int(prim_base.shape[0])
    row = np.ascontiguousarray(row, np.int64)
    col = np.ascontiguousarray(col, np.int64)
    col_slot_map = np.ascontiguousarray(col_slot_map, np.int64)
    prim_base = np.ascontiguousarray(prim_base, np.int64)
    v_base = np.ascontiguousarray(v_base, np.int64)
    vc_e = np.ascontiguousarray(vc_e, np.int64)
    if flat_cols.dtype != np.int32 or not flat_cols.flags.c_contiguous:
        raise ValueError("fill_entries: flat_cols must be contiguous int32")
    if (flat_vals is None) != (val is None):
        raise ValueError("fill_entries: val and flat_vals must be "
                         "both present or both None")
    if flat_vals is not None:
        val = np.ascontiguousarray(val, np.float32)
        if flat_vals.dtype != np.float32 or not flat_vals.flags.c_contiguous:
            raise ValueError(
                "fill_entries: flat_vals must be contiguous float32")
    cursor = np.empty(n_rows, np.int64)

    def p(a, ct):
        return None if a is None else a.ctypes.data_as(ctypes.POINTER(ct))

    rc = lib.pio_fill_entries(
        p(row, ctypes.c_int64), p(col, ctypes.c_int64),
        p(val, ctypes.c_float), len(row),
        p(col_slot_map, ctypes.c_int64), len(col_slot_map),
        p(prim_base, ctypes.c_int64), p(v_base, ctypes.c_int64),
        p(vc_e, ctypes.c_int64), p(cursor, ctypes.c_int64), n_rows,
        p(flat_cols, ctypes.c_int32), p(flat_vals, ctypes.c_float),
        len(flat_cols),
    )
    if rc != 0:
        raise ValueError(
            f"fill_entries: {_FILL_ERRORS.get(rc, f'error {rc}')}")


def tfidf_tf_coo(docs, n_features: int, ngram: int,
                 want_df: bool = False):
    """Native per-doc (feature, count) pairs — the COO twin of
    ``tfidf_tf`` (see pio_tfidf_tf_coo in event_codec.cc). The dense
    [N, D] matrix never exists: linear trainers reduce over docs, so
    only the ~150 distinct buckets per doc need to leave the tokenizer
    (or cross an accelerator link). Returns
    ``(doc_ptr [N+1] int64, feat [nnz] int32, counts [nnz] float32)``
    (+ ``df`` when requested), entries per doc in ascending bucket id.
    """
    lib = _load()
    enc = [d.encode(errors="replace") for d in docs]
    offs = np.zeros(len(enc) + 1, np.int64)
    np.cumsum([len(e) for e in enc], out=offs[1:])
    buf = b"".join(enc)
    # nnz is bounded by token occurrences; every token is >=1 byte with
    # >=0 separators, and each of the (ngram-1) extra orders adds at
    # most one occurrence per token position
    cap = (len(buf) // 2 + len(enc) + 1) * ngram + 1
    doc_ptr = np.zeros(len(enc) + 1, np.int64)
    feat = np.empty(cap, np.int32)
    cnt = np.empty(cap, np.float32)
    df = np.zeros(n_features, np.int64) if want_df else None
    nnz = lib.pio_tfidf_tf_coo(
        buf, offs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        len(enc), n_features, ngram, cap,
        doc_ptr.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        feat.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        cnt.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        (df.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))
         if df is not None else None),
    )
    if nnz < 0:
        raise ValueError(f"tfidf_tf_coo: native tokenizer error {nnz}")
    out = (doc_ptr, feat[:nnz].copy(), cnt[:nnz].copy())
    return out + (df,) if want_df else out


def tfidf_tf(docs, n_features: int, ngram: int,
             want_df: bool = False):
    """Native term-frequency rows (see pio_tfidf_tf in event_codec.cc).

    Bit-identical to ops/tfidf.TfIdfVectorizer's Python token loop.
    ``want_df=True`` returns ``(tf, df)`` with the per-bucket document
    frequency accumulated during the same pass (the IDF fit then needs
    no second sweep over the [N,D] matrix). Raises NativeUnavailable
    when no toolchain.
    """
    lib = _load()
    # errors="replace": lone surrogates (legal in Python str, e.g. out
    # of json.loads "\ud800" escapes) can't encode to UTF-8. '?' is not
    # a token byte, and neither is a surrogate under the Python
    # tokenizer's ASCII class — both act as separators, so replacement
    # preserves token boundaries and bit-identity with the fallback.
    enc = [d.encode(errors="replace") for d in docs]
    offs = np.zeros(len(enc) + 1, np.int64)
    np.cumsum([len(e) for e in enc], out=offs[1:])
    buf = b"".join(enc)
    out = np.zeros((len(enc), n_features), np.float32)
    df = np.zeros(n_features, np.int64) if want_df else None
    rc = lib.pio_tfidf_tf(
        buf, offs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        len(enc), n_features, ngram,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        (df.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))
         if df is not None else None),
    )
    if rc != 0:
        raise ValueError(f"tfidf_tf: native tokenizer error {rc}")
    return (out, df) if want_df else out


def _scan_object_bytes(rec: bytes, start: int) -> int:
    """End index (exclusive) of the JSON object opening at rec[start] == '{'.
    Structural bytes are ASCII, so scanning raw UTF-8 is safe."""
    depth, j = 0, start
    in_str = esc = False
    while j < len(rec):
        ch = rec[j:j + 1]
        if in_str:
            if esc:
                esc = False
            elif ch == b"\\":
                esc = True
            elif ch == b'"':
                in_str = False
        elif ch == b'"':
            in_str = True
        elif ch == b"{":
            depth += 1
        elif ch == b"}":
            depth -= 1
            if depth == 0:
                return j + 1
        j += 1
    raise EventParseError("unterminated properties object")


def parse_events_jsonl_py(buf: bytes) -> ColumnarEvents:
    """Pure-Python reference implementation (fallback + equality oracle).

    Line-delimited only (one JSON object per line) — the format the JSONL
    backend writes. The native parser additionally accepts arbitrary
    inter-object whitespace.
    """
    import datetime as _dt

    from ..data.storage.event import parse_event_time

    tables: list[list[str]] = [[] for _ in range(6)]
    interns: list[dict[str, int]] = [{} for _ in range(6)]

    def intern(which: int, s: str) -> int:
        m = interns[which]
        code = m.get(s)
        if code is None:
            code = len(m)
            m[s] = code
            tables[which].append(s)
        return code

    cols = {k: [] for k in ("event", "etype", "eid", "tetype", "teid",
                            "event_id", "time_us", "rating")}
    props, span, tombstones, tombstone_pos = [], [], [], []
    epoch = _dt.datetime(1970, 1, 1, tzinfo=_dt.timezone.utc)

    offset = 0
    for raw_line in buf.split(b"\n"):
        line = raw_line.strip()
        if not line:
            offset += len(raw_line) + 1
            continue
        lead = len(raw_line) - len(raw_line.lstrip())
        start = offset + lead
        stop = start + len(line)
        try:
            obj = json.loads(line)
        except json.JSONDecodeError as e:
            raise EventParseError(f"{e} at byte {start}") from e
        offset += len(raw_line) + 1
        if not isinstance(obj, dict):
            raise EventParseError(f"expected event object at byte {start}")
        if "__tombstone__" in obj:
            tombstones.append(obj["__tombstone__"])
            tombstone_pos.append(len(cols["event"]))
            continue
        cols["event"].append(intern(0, obj["event"]) if "event" in obj else -1)
        cols["etype"].append(intern(1, obj["entityType"]) if "entityType" in obj else -1)
        cols["eid"].append(intern(2, obj["entityId"]) if "entityId" in obj else -1)
        tet, tei = obj.get("targetEntityType"), obj.get("targetEntityId")
        cols["tetype"].append(intern(3, tet) if tet is not None else -1)
        cols["teid"].append(intern(4, tei) if tei is not None else -1)
        eid = obj.get("eventId")
        cols["event_id"].append(intern(5, eid) if eid is not None else -1)
        t = obj.get("eventTime")
        if t is None:
            cols["time_us"].append(np.iinfo(np.int64).min)
        else:
            try:
                dt = parse_event_time(t)
                cols["time_us"].append(
                    int(round((dt - epoch).total_seconds() * 1e6))
                )
            except Exception:
                cols["time_us"].append(np.iinfo(np.int64).min)
        p = obj.get("properties")
        has_rating = isinstance(p, dict) and "rating" in p
        r = p.get("rating") if has_rating else None
        if isinstance(r, (int, float)) and not isinstance(r, bool):
            try:
                f = np.float32(r)  # float32-range finiteness (codec parity)
            except OverflowError:
                f = np.float32(np.inf)
            cols["rating"].append(float(f) if np.isfinite(f) else -np.inf)
        elif isinstance(r, str) and not set(r) - set("0123456789.+-eE \t\r\n"):
            # string-typed numeric rating; charset limited to what both
            # float() and strtod parse identically (no hex/inf/nan/_)
            try:
                f = np.float32(float(r))
                cols["rating"].append(float(f) if np.isfinite(f) else -np.inf)
            except (ValueError, OverflowError):
                cols["rating"].append(-np.inf)
        elif has_rating:
            # bool / null / list / dict / "1_0": present but unusable
            cols["rating"].append(-np.inf)
        else:
            cols["rating"].append(np.nan)
        if isinstance(p, dict):
            # locate the top-level "properties" key: preceding non-ws byte
            # must be '{' or ',' (an occurrence inside a string value is
            # always preceded by a backslash-escaped quote instead)
            rel = -1
            search = 0
            while True:
                cand = line.find(b'"properties"', search)
                if cand < 0:
                    break
                k = cand - 1
                while k >= 0 and line[k:k + 1] in b" \t":
                    k -= 1
                if k >= 0 and line[k:k + 1] in b"{,":
                    rel = cand
                    break
                search = cand + 1
            brace = line.index(b"{", rel) if rel >= 0 else -1
            if brace >= 0:
                pend = _scan_object_bytes(line, brace)
                props.append((start + brace, start + pend))
            else:
                props.append((-1, -1))
        else:
            props.append((-1, -1))
        span.append((start, stop))

    count = len(cols["event"])
    return ColumnarEvents(
        raw=buf,
        event=np.asarray(cols["event"], np.int32),
        etype=np.asarray(cols["etype"], np.int32),
        eid=np.asarray(cols["eid"], np.int32),
        tetype=np.asarray(cols["tetype"], np.int32),
        teid=np.asarray(cols["teid"], np.int32),
        event_id=np.asarray(cols["event_id"], np.int32),
        time_us=np.asarray(cols["time_us"], np.int64),
        rating=np.asarray(cols["rating"], np.float32),
        props=np.asarray(props, np.int64).reshape(count, 2),
        span=np.asarray(span, np.int64).reshape(count, 2),
        _tables=tables,
        tombstones=tombstones,
        tombstone_pos=np.asarray(tombstone_pos, np.int64),
        parse_stats={"mode": "whole", "pieces": 1, "threads": 1,
                     "merge_ms": 0.0},
    )


def parse_events(buf: bytes, pieces: Optional[int] = None) -> ColumnarEvents:
    """Native when possible, Python otherwise (always one pass)."""
    try:
        return parse_events_jsonl(buf, pieces)
    except NativeUnavailable:
        return parse_events_jsonl_py(buf)


def ingest_batch(raw: bytes, max_items: int, creation_iso: str):
    """Validate + canonicalize a /batch/events.json body in ONE native
    pass (the ★ ingestion hot path). Returns (event_ids, jsonl_bytes) on
    the uniform happy case, or None when ANY item needs the Python path
    (validation failure, client-supplied eventId, over-cap count, syntax
    error) — the caller then re-parses in Python for exact error
    semantics. Raises NativeUnavailable when the codec is not RESIDENT:
    unlike every other entry point this one never triggers the lazy
    build — its callers (/batch handler, inline group commit) can run
    on the event loop, where a first-use g++ build would stall every
    connection for seconds. IngestBuffer warms the codec at
    construction; until someone does, callers fall back to the Python
    path exactly as if no toolchain existed."""
    import os as _os2

    lib = loaded()
    if lib is None:
        raise NativeUnavailable(
            "native codec not resident — warm it off the hot path "
            "(native.available() in a sync context) before first use")
    try:
        # Python json.loads decodes the body as strict UTF-8 before any
        # grammar check; the C scanner is byte-oriented, so invalid UTF-8
        # must bounce to the Python path here or it would be persisted.
        raw.decode("utf-8", "strict")
    except UnicodeDecodeError:
        return None
    ids_hex = _os2.urandom(16 * max_items).hex().encode()
    err = ctypes.create_string_buffer(256)
    h = lib.pio_ingest_batch(raw, len(raw), ids_hex, max_items,
                             creation_iso.encode(), err, len(err))
    if not h:
        return None
    try:
        if not lib.pio_ingest_all_ok(h):
            return None
        n = lib.pio_ingest_count(h)
        nbytes = ctypes.c_int64()
        ptr = lib.pio_ingest_lines(h, ctypes.byref(nbytes))
        lines = ctypes.string_at(ptr, nbytes.value)
        ids = [ids_hex[32 * j:32 * (j + 1)].decode() for j in range(n)]
        return ids, lines
    finally:
        lib.pio_ingest_free(h)


def cco_partition(u: np.ndarray, i: np.ndarray, rank, n_users: int,
                  u_chunk: int, n_ranges: int, n_items: int,
                  h_chunk: int, h_ranges: int):
    """One-pass C partition of deduped user-sorted (u, i) pairs into the
    CCO slab layout (ops/llr.py): ((light_eu, light_ei), (heavy_eu,
    heavy_ei) or None, item_counts), in place of the numpy version's
    fancy-index scatter + bincounts. With the start of the slabs'
    uploads, one call is 0.26 s at 10.0M pairs and 0.53-0.63 s at 20.0M,
    two calls side by side (``ops/llr._prepare_events``, span
    ``cco.partition.event``; chip host, PR 32). Releases the GIL and
    keeps no state between calls. Requires the uint16 wire (u_chunk <
    0xFFFF, n_items <= 0xFFFF); raises NativeUnavailable otherwise or
    when the codec cannot load — callers fall back to numpy (identical
    layout, tested)."""
    if u_chunk >= 0xFFFF or n_items > 0xFFFF or h_chunk >= 0xFFFF:
        raise NativeUnavailable("cco_partition: ids exceed the uint16 wire")
    lib = _load()
    u = np.ascontiguousarray(u, np.int32)
    i = np.ascontiguousarray(i, np.int32)
    rank_ptr = None
    if rank is not None:
        rank = np.ascontiguousarray(rank, np.int32)
        rank_ptr = rank.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))
    h = lib.pio_cco_partition(
        u.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        i.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        u.size, rank_ptr, n_users, u_chunk, n_ranges, n_items,
        h_chunk, h_ranges if rank is not None else 0)
    if not h:
        raise NativeUnavailable("cco_partition failed")
    try:
        le = lib.pio_ccop_dim(h, 0)
        light = tuple(
            np.ctypeslib.as_array(lib.pio_ccop_slab(h, w),
                                  shape=(n_ranges, le)).copy()
            for w in (0, 1))
        heavy = None
        if rank is not None:
            he = lib.pio_ccop_dim(h, 1)
            heavy = tuple(
                np.ctypeslib.as_array(lib.pio_ccop_slab(h, w),
                                      shape=(h_ranges, he)).copy()
                for w in (2, 3))
        counts = np.ctypeslib.as_array(
            lib.pio_ccop_item_counts(h), shape=(n_items,)).copy()
        return light, heavy, counts
    finally:
        lib.pio_ccop_free(h)

def pair_dedupe(u: np.ndarray, i: np.ndarray, n_users: int, n_items: int):
    """Distinct (user, item) pairs sorted by (user, item) + per-user
    distinct counts, via counting-sort by user + small per-user sorts —
    two linear passes in place of np.unique's global comparison sort:
    0.51 s at 10.0M events and 1.07 s at 20.0M, two calls side by side
    (span ``cco.dedupe.event``; chip host, PR 32). Releases the GIL and
    keeps no state between calls. Identical output order to the
    packed-key np.unique (tested). Raises NativeUnavailable when the
    codec cannot load."""
    lib = _load()
    u = np.asarray(u)
    i = np.asarray(i)
    if u.dtype != np.int32 or i.dtype != np.int32:
        # range-check in the WIDE dtype first: an unsafe int64→int32
        # cast would wrap an out-of-range id INTO the valid range and
        # keep a pair the numpy fallback drops
        u64 = u.astype(np.int64)
        i64 = i.astype(np.int64)
        valid = ((u64 >= 0) & (u64 < n_users)
                 & (i64 >= 0) & (i64 < n_items))
        u = u64[valid].astype(np.int32)
        i = i64[valid].astype(np.int32)
    u = np.ascontiguousarray(u, np.int32)
    i = np.ascontiguousarray(i, np.int32)
    h = lib.pio_pair_dedupe(
        u.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        i.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        u.size, n_users, n_items)
    if not h:
        raise NativeUnavailable("pair_dedupe failed")
    try:
        n = lib.pio_pdd_count(h)
        if n:  # empty vectors hand back NULL data pointers
            du = np.ctypeslib.as_array(lib.pio_pdd_users(h), shape=(n,)).copy()
            di = np.ctypeslib.as_array(lib.pio_pdd_items(h), shape=(n,)).copy()
        else:
            du = np.zeros(0, np.int32)
            di = np.zeros(0, np.int32)
        per_user = (np.ctypeslib.as_array(
            lib.pio_pdd_per_user(h), shape=(n_users,)).copy()
            if n_users else np.zeros(0, np.int64))
        return du, di, per_user
    finally:
        lib.pio_pdd_free(h)
