"""The host half of a cross-occurrence train (``ops/llr._prepare_events``):
the events' dedupe and slab layout run side by side unless PIO_PIPELINE=off,
and nothing but the wall may differ — the same slabs reach the device
program, the same indicators come back, the spans say what ran and how, a
failure in one event's pass reaches the caller with no thread left behind,
and a wrapper put on ``llr._dedupe_pair`` from outside (the benchmark's
traced run) still sees one call an event. No assertion on wall time."""

import threading

import jax
import numpy as np
import pytest

from incubator_predictionio_tpu.common import telemetry
from incubator_predictionio_tpu.ops import llr

N_USERS, N_ITEMS, U_CHUNK, K = 1500, 200, 128, 20


def seeded_events():
    """Three events over one item space: repeated pairs (zipfian items),
    two users who did nearly everything (over 16 times the mean and over 256
    distinct pairs, so the heavy scan runs) and ids out of range at both
    ends, which the dedupe drops."""
    rng = np.random.default_rng(32)
    events = {}
    for name, n in (("buy", 8000), ("view", 20000), ("cart", 6000)):
        u = rng.integers(0, N_USERS, n).astype(np.int32)
        i = (rng.zipf(1.3, n) % N_ITEMS).astype(np.int32)
        for who in (7, 900):
            mine = rng.permutation(N_ITEMS)[:150].astype(np.int32)
            u = np.concatenate([u, np.full(len(mine), who, np.int32)])
            i = np.concatenate([i, mine])
        u = np.concatenate([u, np.int32([-1, N_USERS, N_USERS + 5, 3, 4])])
        i = np.concatenate([i, np.int32([2, 3, 4, -2, N_ITEMS + 3])])
        events[name] = (u, i)
    return events


def run_multi(events, mesh=None):
    """The fused path: primary ``buy``, secondaries ``buy`` (the self pair,
    the primary's own arrays), ``view`` and ``cart``."""
    return llr.cco_indicators_multi(
        *events["buy"], events, n_users=N_USERS, n_items=N_ITEMS,
        max_correlators=K, u_chunk=U_CHUNK, item_block=64, mesh=mesh)


def run_pair(events, mesh=None):
    return {"view": llr.cco_indicators(
        *events["buy"], *events["view"], N_USERS, N_ITEMS,
        max_correlators=K, u_chunk=U_CHUNK, item_block=64, mesh=mesh)}


def run_mesh(events):
    from incubator_predictionio_tpu.parallel.mesh import mesh_from_devices

    return run_multi(events, mesh_from_devices(devices=jax.devices("cpu")))


#: entry point -> (call, its distinct events as the spans name them)
ENTRIES = {"multi": (run_multi, [0, "view", "cart"]),
           "pair": (run_pair, [0, 1]),
           "mesh": (run_mesh, [0, "view", "cart"])}


@pytest.fixture(autouse=True)
def fused_fits(monkeypatch):
    monkeypatch.setenv("PIO_UR_FULL_MATRIX_ELEMS", str(10 * N_ITEMS ** 2))
    monkeypatch.delenv("PIO_PIPELINE", raising=False)


def span_mark() -> int:
    """The highest span id handed out so far: a cursor that still works
    once the ring is full (its length then stands still; another test
    file of the same worker may have filled it)."""
    return max((s.span_id for s in telemetry.spans_snapshot()), default=0)


def spans_since(seen: int, prefix: str = "cco."):
    return [s for s in telemetry.spans_snapshot()
            if s.span_id > seen and s.name.startswith(prefix)]


@pytest.mark.parametrize("entry", list(ENTRIES))
def test_side_by_side_and_in_turn_agree_bit_for_bit(monkeypatch, entry):
    run, distinct = ENTRIES[entry]
    events = seeded_events()
    handed = []
    count = llr._cco_count_multi

    def recording(*slabs, **static):
        handed.append((jax.tree.map(np.asarray, slabs),
                       static["self_flags"]))
        return count(*slabs, **static)

    monkeypatch.setattr(llr, "_cco_count_multi", recording)
    got = {}
    for mode in ("auto", "off"):
        monkeypatch.setenv("PIO_PIPELINE", mode)
        seen = span_mark()
        got[mode] = run(events)
        stages = [s for s in spans_since(seen)
                  if s.name in ("cco.dedupe", "cco.partition")]
        assert [s.tags["threads"] for s in stages] == (
            [len(distinct)] * 2 if mode == "auto" else [1, 1])

    assert got["auto"].keys() == got["off"].keys()
    for name in got["auto"]:
        np.testing.assert_array_equal(got["auto"][name].idx,
                                      got["off"][name].idx, err_msg=name)
        np.testing.assert_array_equal(got["auto"][name].score,
                                      got["off"][name].score, err_msg=name)
        assert (got["auto"][name].idx >= 0).any()

    (slabs_auto, flags_auto), (slabs_off, flags_off) = handed
    assert flags_auto == flags_off
    leaves_auto, tree_auto = jax.tree.flatten(slabs_auto)
    leaves_off, tree_off = jax.tree.flatten(slabs_off)
    assert tree_auto == tree_off
    # the heavy scan ran: the primary's heavy slabs are there
    assert len(slabs_auto[2]) == 2
    for a, b in zip(leaves_auto, leaves_off):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("mode", ["auto", "off"])
@pytest.mark.parametrize("entry", ["multi", "pair"])
def test_one_span_a_stage_and_one_child_an_event(monkeypatch, entry, mode):
    run, distinct = ENTRIES[entry]
    monkeypatch.setenv("PIO_PIPELINE", mode)
    events = seeded_events()
    seen = span_mark()
    with telemetry.span("train.run", trace_id="train-32") as root:
        run(events)
    spans = spans_since(seen)
    assert {s.trace_id for s in spans} == {"train-32"}
    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    threads = len(distinct) if mode == "auto" else 1
    for stage in ("cco.dedupe", "cco.partition"):
        parent, = by_name[stage]
        assert parent.parent_id == root.span_id
        assert parent.tags == {"events": len(distinct), "threads": threads}
        kids = by_name[stage + ".event"]
        # one an event, none for the self pair, each beneath its stage
        assert sorted(map(str, (k.tags["event"] for k in kids))) == sorted(
            map(str, distinct))
        assert all(k.parent_id == parent.span_id for k in kids)
        assert all(parent.t0_ns <= k.t0_ns and k.t1_ns <= parent.t1_ns
                   for k in kids)
        assert all(k.tags["pairs"] > 0 for k in kids)
    raw = {k.tags["event"]: k.tags["pairs"]
           for k in by_name["cco.dedupe.event"]}
    kept = {k.tags["event"]: k.tags["pairs"]
            for k in by_name["cco.partition.event"]}
    assert raw[0] == len(events["buy"][0])
    assert all(kept[e] < raw[e] for e in distinct)   # repeats, bad ids


def test_a_self_pair_alone_runs_on_the_calling_thread():
    buy = seeded_events()["buy"]
    seen = span_mark()
    got = llr.cco_indicators_multi(
        *buy, {"buy": buy, "again": buy}, n_users=N_USERS, n_items=N_ITEMS,
        max_correlators=K, u_chunk=U_CHUNK, item_block=64)
    np.testing.assert_array_equal(got["buy"].score, got["again"].score)
    spans = spans_since(seen)
    for stage in ("cco.dedupe", "cco.partition"):
        parent, = [s for s in spans if s.name == stage]
        assert parent.tags == {"events": 1, "threads": 1}
        kid, = [s for s in spans if s.name == stage + ".event"]
        assert kid.tags["event"] == 0 and kid.parent_id == parent.span_id


class Planted(RuntimeError):
    pass


@pytest.mark.parametrize("entry", ["multi", "pair"])
@pytest.mark.parametrize("stage", ["dedupe", "partition"])
def test_a_failure_in_one_event_reaches_the_caller(monkeypatch, stage,
                                                   entry):
    run, _distinct = ENTRIES[entry]
    events = seeded_events()
    n_view = len(events["view"][0])
    if stage == "dedupe":
        real = llr._dedupe_pair

        def dedupe(u, i, n_users, n_items):
            if len(u) == n_view:
                raise Planted("view")
            return real(u, i, n_users, n_items)

        monkeypatch.setattr(llr, "_dedupe_pair", dedupe)
    else:
        # the native layout's failure falls back to NumPy's, by design:
        # plant the fault in both
        native = pytest.importorskip("incubator_predictionio_tpu.native")
        monkeypatch.setattr(native, "cco_partition",
                            lambda *a, **kw: 1 / 0)
        real = llr._layout_event
        n_kept = len(llr._dedupe_pair(*events["view"], N_USERS, N_ITEMS)[0])

        def layout(u, *rest):
            if len(u) == n_kept:
                raise Planted("view")
            return real(u, *rest)

        monkeypatch.setattr(llr, "_layout_event", layout)
    with pytest.raises(Planted):
        run(events)
    assert not [t for t in threading.enumerate()
                if t.name.startswith("pio-hostpar")]


@pytest.mark.parametrize("entry", ["multi", "pair"])
def test_a_wrapper_on_dedupe_pair_sees_one_call_an_event(monkeypatch, entry):
    """As the benchmark's traced run wraps it (``wrap_span``: the module's
    attribute is replaced from outside, for one run)."""
    run, distinct = ENTRIES[entry]
    events = seeded_events()
    real, calls, lock = llr._dedupe_pair, [], threading.Lock()

    def counting(u, i, *rest):
        with lock:
            calls.append((len(u), threading.current_thread().name))
        return real(u, i, *rest)

    monkeypatch.setattr(llr, "_dedupe_pair", counting)
    run(events)
    names = {0: "buy", 1: "view"}
    assert sorted(n for n, _t in calls) == sorted(
        len(events[names.get(e, e)][0]) for e in distinct)
    # and they ran on the helper's threads, not in turn on this one
    assert all(t.startswith("pio-hostpar") for _n, t in calls)
