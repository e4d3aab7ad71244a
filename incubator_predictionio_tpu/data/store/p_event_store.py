"""PEventStore — bulk event reads for training DataSources.

Reference: data/.../data/store/PEventStore.scala (find/aggregateProperties
returning RDDs). The TPU-native analog returns *columnar batches*: entity
ids and values as numpy arrays plus BiMaps, ready for device sharding —
the "RDD[Event] → device array" bridge of SURVEY.md §7 step 4.
"""

from __future__ import annotations

import dataclasses
import datetime as _dt
from typing import Iterator, Optional, Sequence

import numpy as np

from ...common import telemetry
from ...native import IdTable
from ..storage.bimap import BiMap
from ..storage.datamap import PropertyMap
from ..storage.event import Event
from ..storage.registry import Storage


@dataclasses.dataclass
class EventBatch:
    """Columnar view of an event scan (host side)."""

    event: list[str]
    entity_type: list[str]
    entity_id: list[str]
    target_entity_id: list[Optional[str]]
    properties: list[dict]
    event_time_us: np.ndarray  # int64 epoch micros

    def __len__(self) -> int:
        return len(self.event)


_EPOCH = _dt.datetime(1970, 1, 1, tzinfo=_dt.timezone.utc)


def _resolve_app(app_name: str, storage: Optional[Storage] = None,
                 channel_name: Optional[str] = None):
    """app name (+channel name) → ids (reference: Common.appNameToId)."""
    s = storage or Storage.instance()
    app = s.get_meta_data_apps().get_by_name(app_name)
    if app is None:
        raise ValueError(f"App {app_name!r} does not exist; create it with `pio app new`")
    channel_id = None
    if channel_name:
        chans = [c for c in s.get_meta_data_channels().get_by_appid(app.id)
                 if c.name == channel_name]
        if not chans:
            raise ValueError(f"Channel {channel_name!r} not found for app {app_name!r}")
        channel_id = chans[0].id
    return s, app.id, channel_id


class PEventStore:
    """Static facade mirroring the reference object's API."""

    @staticmethod
    def find(
        app_name: str,
        channel_name: Optional[str] = None,
        start_time: Optional[_dt.datetime] = None,
        until_time: Optional[_dt.datetime] = None,
        entity_type: Optional[str] = None,
        entity_id: Optional[str] = None,
        event_names: Optional[Sequence[str]] = None,
        target_entity_type: Optional[str] = None,
        target_entity_id: Optional[str] = None,
        storage: Optional[Storage] = None,
    ) -> Iterator[Event]:
        s, app_id, channel_id = _resolve_app(app_name, storage, channel_name)
        return s.get_p_events().find(
            app_id, channel_id, start_time, until_time, entity_type,
            entity_id, event_names, target_entity_type, target_entity_id,
        )

    @staticmethod
    def find_batches(
        app_name: str,
        event_names: Optional[Sequence[str]] = None,
        storage: Optional[Storage] = None,
        chunk_size: int = 65536,
        **kwargs,
    ) -> Iterator[EventBatch]:
        """Chunked columnar scan: yields EventBatch slices of at most
        ``chunk_size`` events in scan order. This is the batch iterator
        the streaming input pipeline's featurize workers pull from
        (workflow/input_pipeline.prefetch) — decode of chunk N+1
        overlaps featurize/upload of chunk N instead of the whole scan
        materializing first. Concatenating the chunks reproduces
        find_batch exactly.

        A training read that passes no time range fills it from the
        ambient training window (``pio train --window`` /
        ``PIO_TRAIN_WINDOW``); explicit bounds are never
        overridden."""
        from ...common import train_window

        start, until = train_window.apply_window(
            kwargs.get("start_time"), kwargs.get("until_time"))
        if start is not None or until is not None:
            kwargs = dict(kwargs, start_time=start, until_time=until)
        events = PEventStore.find(
            app_name, event_names=event_names, storage=storage, **kwargs
        )
        step = max(1, int(chunk_size))
        ev, et, eid, tid, props, times = [], [], [], [], [], []

        def flush() -> EventBatch:
            return EventBatch(
                event=ev, entity_type=et, entity_id=eid,
                target_entity_id=tid, properties=props,
                event_time_us=np.asarray(times, dtype=np.int64),
            )

        for e in events:
            ev.append(e.event)
            et.append(e.entity_type)
            eid.append(e.entity_id)
            tid.append(e.target_entity_id)
            props.append(e.properties.to_dict())
            times.append(
                int((e.event_time - _EPOCH).total_seconds() * 1_000_000)
            )
            if len(ev) >= step:
                yield flush()
                ev, et, eid, tid, props, times = [], [], [], [], [], []
        if ev:
            yield flush()

    @staticmethod
    def find_batch(
        app_name: str,
        event_names: Optional[Sequence[str]] = None,
        storage: Optional[Storage] = None,
        **kwargs,
    ) -> EventBatch:
        """Columnar scan (the hot path for DataSources) — the
        concatenation of find_batches."""
        ev, et, eid, tid, props = [], [], [], [], []
        times: list[np.ndarray] = []
        for b in PEventStore.find_batches(
                app_name, event_names=event_names, storage=storage, **kwargs):
            ev += b.event
            et += b.entity_type
            eid += b.entity_id
            tid += b.target_entity_id
            props += b.properties
            times.append(b.event_time_us)
        return EventBatch(
            event=ev, entity_type=et, entity_id=eid, target_entity_id=tid,
            properties=props,
            event_time_us=(np.concatenate(times) if times
                           else np.asarray([], dtype=np.int64)),
        )

    @staticmethod
    def find_ratings(
        app_name: str,
        event_names: Optional[Sequence[str]] = None,
        rating_from_props: bool = True,
        default_rating: float = 1.0,
        event_default_ratings: Optional[dict] = None,
        storage: Optional[Storage] = None,
        channel_name: Optional[str] = None,
        start_time: Optional[_dt.datetime] = None,
        until_time: Optional[_dt.datetime] = None,
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray, BiMap, BiMap]:
        """(user, item, rating) COO triple + id maps — the shared prep for
        every recommendation-family template.

        Fast path: when the event backend exposes a columnar scan (the
        JSONL log decoded by the native codec — data/storage/jsonl.py),
        the triple is assembled with pure numpy on interned codes, never
        materializing per-event Python objects. Otherwise falls back to
        the row-based scan + ``ratings_matrix``.

        ``event_default_ratings`` assigns a rating to events of a given
        name when properties carry none (e.g. the quickstart template's
        implicit "buy" → 4.0).

        When neither ``start_time`` nor ``until_time`` is given the
        ambient training window (``pio train --window`` /
        ``PIO_TRAIN_WINDOW``) applies; explicit bounds win.
        """
        from ...common import train_window

        start_time, until_time = train_window.apply_window(
            start_time, until_time)
        s, app_id, channel_id = _resolve_app(app_name, storage, channel_name)
        pe = s.get_p_events()
        if hasattr(pe, "scan_columnar"):
            cols, rows = pe.scan_columnar(
                app_id, channel_id, event_names, start_time, until_time
            )
            # span store.select: the selection put in time order and
            # its ratings filled in (the masks are scan_columnar's)
            with telemetry.span("store.select", step="order"):
                # malformed records: no entityId
                rows = rows[cols.eid[rows] >= 0]
                # The row path iterates events time-sorted (LEvents.find
                # semantics); order the selection the same way so BiMap
                # first-seen index assignment matches bit-for-bit.
                rows = rows[np.argsort(cols.time_us[rows], kind="stable")]
                # BiMap membership and index order must match the row path
                # exactly: users cover ALL scanned events (even target-less
                # ones), items only events with a target; both indexed in
                # first-seen order within the selection (BiMap.string_int).
                keep_mask = cols.teid[rows] >= 0
                keep = rows[keep_mask]
                if rating_from_props:
                    r = cols.rating[keep].astype(np.float32, copy=True)
                    # Codec sentinel semantics: NaN = "rating" key absent
                    # (event-default applies, like the row path injecting into
                    # properties), -inf = key present but not coercible
                    # (row path's _coerce → plain default_rating).
                    missing = np.isnan(r)
                    unusable = np.isneginf(r)
                    if unusable.any():
                        r[unusable] = np.float32(default_rating)
                    if missing.any():
                        fill = np.full(keep.shape, np.float32(default_rating))
                        if event_default_ratings:
                            ev_table = cols.table(cols.TABLE_EVENT)
                            ev = cols.event[keep]
                            for name, val in event_default_ratings.items():
                                if name in ev_table:
                                    fill = np.where(
                                        ev == ev_table.index(name),
                                        np.float32(val), fill,
                                    )
                        r[missing] = fill[missing]
                else:
                    r = np.full(keep.shape, default_rating, np.float32)

            def densify(codes: np.ndarray, which: int):
                """Codes to dense rows in first-seen order, and the
                map of the rows' ids. A code is bounded by its table's
                size, so no sort of the events: the reversed scatter
                leaves every code its FIRST position (of a repeated
                index the last assignment stays), and only the codes
                present are sorted, by that position."""
                first = np.full(cols.table_size(which), -1, np.int64)
                first[codes[::-1]] = np.arange(len(codes) - 1, -1, -1)
                present = np.nonzero(first >= 0)[0]
                row_codes = present[np.argsort(first[present])]
                rank = np.empty(first.shape, np.int32)
                rank[row_codes] = np.arange(row_codes.shape[0],
                                            dtype=np.int32)
                ids = cols.take(which, row_codes)
                arrays = isinstance(ids, IdTable)
                return rank[codes], arrays, BiMap(
                    ids if arrays else {s: k for k, s in enumerate(ids)})

            # span store.index: codes to dense rows in first-seen
            # order and the id maps of both sides; ``ids`` says what
            # backs the maps: the codec's arrays, or strings (tables
            # that were lists: a snapshot, an extended scan)
            with telemetry.span("store.index") as sp:
                u_all, u_arrays, users = densify(
                    cols.eid[rows], cols.TABLE_EID)
                u = u_all[keep_mask]
                i, i_arrays, items = densify(
                    cols.teid[keep], cols.TABLE_TEID)
                sp.tag(users=len(users), items=len(items),
                       ids="arrays" if u_arrays and i_arrays
                       else "strings")
            return u, i, r, users, items

        batch = PEventStore.find_batch(
            app_name, event_names=event_names, storage=storage,
            channel_name=channel_name, start_time=start_time,
            until_time=until_time,
        )
        if rating_from_props and event_default_ratings:
            for j, ev in enumerate(batch.event):
                dflt = event_default_ratings.get(ev)
                if dflt is not None and "rating" not in batch.properties[j]:
                    batch.properties[j] = {**batch.properties[j], "rating": dflt}
        return ratings_matrix(
            batch, rating_from_props=rating_from_props,
            default_rating=default_rating,
        )

    @staticmethod
    def aggregate_properties(
        app_name: str,
        entity_type: str,
        channel_name: Optional[str] = None,
        start_time: Optional[_dt.datetime] = None,
        until_time: Optional[_dt.datetime] = None,
        required: Optional[Sequence[str]] = None,
        storage: Optional[Storage] = None,
    ) -> dict[str, PropertyMap]:
        s, app_id, channel_id = _resolve_app(app_name, storage, channel_name)
        return s.get_p_events().aggregate_properties(
            app_id, entity_type, channel_id, start_time, until_time, required
        )


def ratings_matrix(
    batch: EventBatch,
    rating_from_props: bool = True,
    default_rating: float = 1.0,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, BiMap, BiMap]:
    """(user, item, rating) COO triple + id maps from a columnar batch —
    the shared prep for every recommendation-family template."""
    users = BiMap.string_int(batch.entity_id)
    items = BiMap.string_int(t for t in batch.target_entity_id if t is not None)
    u = users.map_array(batch.entity_id)
    i = np.fromiter(
        (items(t) if t is not None else -1 for t in batch.target_entity_id),
        dtype=np.int32,
        count=len(batch),
    )
    if rating_from_props:
        def _coerce(v) -> float:
            # Must mirror the columnar codec exactly (fast/slow parity):
            # bool/None, strings outside the common float()/strtod charset
            # (hex, inf, nan, "1_0"), and values non-finite after the
            # float32 cast all count as "present but unusable".
            if isinstance(v, bool) or v is None:
                return default_rating
            if isinstance(v, str) and set(v) - set("0123456789.+-eE \t\r\n"):
                return default_rating
            try:
                f = np.float32(float(v))
            except (TypeError, ValueError, OverflowError):
                return default_rating
            return float(f) if np.isfinite(f) else default_rating

        r = np.fromiter(
            (_coerce(p.get("rating", default_rating)) for p in batch.properties),
            dtype=np.float32,
            count=len(batch),
        )
    else:
        r = np.full(len(batch), default_rating, dtype=np.float32)
    keep = i >= 0
    return u[keep], i[keep], r[keep], users, items
