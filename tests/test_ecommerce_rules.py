"""The e-commerce template's serve-time rules: stock
``ECommerceModel.recommend`` through a SQLITE event store against a plain
reference on seeded random factors. What the user has seen and what the
``unavailableItems`` constraint withdraws come from two blocking store reads
a query; categories, whiteList and blackList from the query. The catalog is
large enough (3,000 items, num 10) for ``_topk_scores`` to take the block
selection, so whole blocks at -inf are selected under too. On the ``flat``
layout the rules reach the kernel as rows and a resident category mask
(path ``device``; tests/test_topk_rows.py holds that form to the dense
one); on the ``mesh`` layout as the dense host mask.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from incubator_predictionio_tpu.common import telemetry  # noqa: E402
from incubator_predictionio_tpu.data.storage import base  # noqa: E402
from incubator_predictionio_tpu.data.storage.bimap import BiMap  # noqa: E402
from incubator_predictionio_tpu.data.storage.datamap import (  # noqa: E402
    DataMap,
)
from incubator_predictionio_tpu.data.storage.event import Event  # noqa: E402
from incubator_predictionio_tpu.data.storage.registry import (  # noqa: E402
    Storage,
)
from incubator_predictionio_tpu.models import _filters  # noqa: E402
from incubator_predictionio_tpu.models.ecommerce import (  # noqa: E402
    ECommerceModel,
)
from incubator_predictionio_tpu.ops import topk  # noqa: E402
from incubator_predictionio_tpu.ops.als import ALSFactors  # noqa: E402

N_USERS, N_ITEMS, RANK = 8, 3000, 16
APP = "RulesShop"
CATS = ("c0", "c1", "c2")


def item(j) -> str:
    return f"i{int(j)}"


class Shop:
    """Seeded factors, each item's category, and a SQLITE store holding u0's
    views and buys (items of u0's own top 10) and a constraint that
    withdraws items of u1's top 10: the model, and the lists the reference
    is built from."""

    def __init__(self, tmp_path):
        rng = np.random.default_rng(34)
        self.users = rng.normal(size=(N_USERS, RANK)).astype(np.float32)
        self.items = rng.normal(size=(N_ITEMS, RANK)).astype(np.float32)
        self.cat = rng.integers(0, len(CATS), N_ITEMS)
        self.scores = self.items @ self.users.T           # [items, users]
        top = np.argsort(-self.scores, axis=0, kind="stable")
        self.seen = {0: set(top[[0, 2, 5], 0].tolist())}
        self.withdrawn = set(top[[1, 3], 1].tolist())
        env = {"PIO_STORAGE_SOURCES_DB_TYPE": "SQLITE",
               "PIO_STORAGE_SOURCES_DB_PATH": str(tmp_path / "pio.sqlite")}
        for repo in ("METADATA", "EVENTDATA"):
            env[f"PIO_STORAGE_REPOSITORIES_{repo}_SOURCE"] = "DB"
        self.storage = Storage(env)
        self.app_id = self.storage.get_meta_data_apps().insert(
            base.App(0, APP, None))
        for n, j in enumerate(sorted(self.seen[0])):
            self.write("u0", j, "buy" if n else "view")
        self.storage.get_l_events().insert(Event(
            event="$set", entity_type="constraint",
            entity_id="unavailableItems", properties=DataMap(
                {"items": [item(j) for j in sorted(self.withdrawn)]})),
            self.app_id)
        self.model = ECommerceModel(
            factors=ALSFactors(self.users, self.items, N_USERS, N_ITEMS),
            users=BiMap.string_int(f"u{u}" for u in range(N_USERS)),
            items=BiMap.string_int(item(j) for j in range(N_ITEMS)),
            item_categories={item(j): {CATS[c]}
                             for j, c in enumerate(self.cat)},
            app_name=APP, seen_event_names=("view", "buy"))
        self.model._storage = self.storage

    def write(self, user: str, j: int, event: str = "view") -> None:
        self.storage.get_l_events().insert(Event(
            event=event, entity_type="user", entity_id=user,
            target_entity_type="item", target_entity_id=item(j)),
            self.app_id)

    def allowed(self, user: int, categories=None, white=None, black=(),
                unseen_only=True) -> np.ndarray:
        """bool[N_ITEMS] by the sparse definition, from the lists alone."""
        ok = np.ones(N_ITEMS, bool)
        if categories:
            ok &= np.isin(self.cat, [CATS.index(c) for c in categories])
        if white:
            known = [int(w[1:]) for w in white if w[1:].isdigit()]
            ok &= np.isin(np.arange(N_ITEMS), known)
        gone = set(self.withdrawn) | {int(b[1:]) for b in black}
        if unseen_only:
            gone |= self.seen.get(user, set())
        ok[sorted(gone)] = False
        return ok

    def want(self, user: int, num: int, ok: np.ndarray):
        order = np.lexsort((np.arange(N_ITEMS), -self.scores[:, user]))
        best = [j for j in order.tolist() if ok[j]][:num]
        return [item(j) for j in best], self.scores[best, user]


@pytest.fixture(scope="module")
def shop(tmp_path_factory):
    s = Shop(tmp_path_factory.mktemp("rules"))
    yield s
    s.storage.close()


def _top(shop, user: int, n: int) -> list[str]:
    return shop.want(user, n, np.ones(N_ITEMS, bool))[0]


#: id -> (user, num, the query's rules); u0 has seen items of its own top,
#: u1's top holds withdrawn items, u2 neither
CASES = {
    "none": (2, 10, {}),
    "seen": (0, 10, {}),
    "seen-filter-off": (0, 10, {"unseen_only": False}),
    "withdrawn": (1, 10, {}),
    "categories": (2, 10, {"categories": ["c1"]}),
    "two-categories": (2, 4, {"categories": ["c0", "c2"]}),
    "whiteList": (2, 10, {"white": [item(j) for j in range(100, 140)]}),
    "blackList": (2, 10, {"black": "own-top-5"}),
    "all": (0, 10, {"categories": ["c0", "c1"], "black": "own-top-5",
                    "white": [item(j) for j in range(0, N_ITEMS, 7)]}),
    "fewer-allowed-than-num": (2, 10, {"white": [item(7), item(1900),
                                                 item(2999)]}),
    "whiteList-of-unknown-ids": (2, 10, {"white": ["nope", "i999999"]}),
    "unknown-user": (None, 10, {}),
}


@pytest.mark.parametrize("case", CASES)
def test_recommend_is_the_top_of_the_allowed(shop, case):
    user, num, rules = CASES[case]
    rules = dict(rules)
    if rules.get("black") == "own-top-5":
        rules["black"] = _top(shop, user, 5)
    assert topk.select_block_len(N_ITEMS, num)          # the block path
    got = shop.model.recommend(
        "nobody" if user is None else f"u{user}", num,
        categories=rules.get("categories"), white_list=rules.get("white"),
        black_list=rules.get("black"),
        unseen_only=rules.get("unseen_only", True))
    if user is None:
        assert got == []
        return
    ok = shop.allowed(user, rules.get("categories"), rules.get("white"),
                      rules.get("black", ()), rules.get("unseen_only", True))
    ids, scores = shop.want(user, num, ok)
    assert [i for i, _s in got] == ids
    np.testing.assert_allclose([s for _i, s in got], scores, rtol=1e-5,
                               atol=1e-5)
    assert len(got) == min(num, int(ok.sum()))
    # and the mask itself, bit for bit, from the same lists
    extra = shop.withdrawn | (shop.seen.get(user, set())
                              if rules.get("unseen_only", True) else set())
    mask = _filters.build_exclude_mask(
        shop.model.items, shop.model.category_index(),
        rules.get("categories"), rules.get("white"), rules.get("black"),
        extra_excluded_items=[item(j) for j in extra])
    np.testing.assert_array_equal(mask, ~ok)


def test_a_write_after_the_model_is_loaded_is_seen_by_the_next_query(shop):
    best = shop.model.recommend("u3", 4)[0][0]
    shop.write("u3", int(best[1:]))
    after = [i for i, _s in shop.model.recommend("u3", 4)]
    shop.seen[3] = {int(best[1:])}
    assert best not in after
    assert after == shop.want(3, 4, shop.allowed(3))[0]


def test_spans_and_the_counter_carry_their_tags(shop, monkeypatch):
    monkeypatch.setattr(telemetry._STATE, "metrics_on", True)
    rule = lambda name: _filters._M_RULES.labels(name).value()
    before = {r: rule(r) for r in ("categories", "whiteList", "blackList",
                                   "extra", "none")}
    device = _filters._M_MASK_PATH.labels("device").value()
    with telemetry.span("test.query") as root:
        shop.model.recommend("u0", 10, categories=["c1"],
                             black_list=[item(3), "nope"])
    mine = [s for s in telemetry.spans_snapshot()
            if s.trace_id == root.trace_id and s.parent_id == root.span_id]
    assert [s.name for s in mine] == [
        "query.store_read", "query.store_read", "query.mask_build",
        "topk.mask_put", "topk.dispatch", "topk.wait"]
    assert [s.tags for s in mine[:2]] == [
        {"what": "unavailable", "events": 1},
        {"what": "seen", "events": len(shop.seen[0])}]
    # the withdrawn, the seen and the one blackList id the catalog knows
    assert mine[2].tags == {
        "rules": "categories+blackList+extra",
        "excluded": len(shop.withdrawn) + len(shop.seen[0]) + 1,
        "path": "device"}
    # the rows at the ladder's floor and the whiteList flag, not N_ITEMS
    assert mine[3].tags == {"bytes": 4 * (2 * topk.ROW_LADDER[0] + 1)}
    assert all(a.t1_ns <= b.t0_ns for a, b in zip(mine, mine[1:]))
    after = {r: rule(r) for r in before}
    assert {r: after[r] - before[r] for r in before} == {
        "categories": 1, "whiteList": 0, "blackList": 1, "extra": 1,
        "none": 0}
    assert _filters._M_MASK_PATH.labels("device").value() == device + 1
    # a mask resident on the device (no rule: the recommendation template's
    # case) takes no put, and its dispatch no new work
    with telemetry.span("test.query") as root:
        topk.top_k_items(shop.users[0], shop.items, 10)
    assert [s.name for s in telemetry.spans_snapshot()
            if s.trace_id == root.trace_id and s.parent_id == root.span_id
            ] == ["topk.dispatch", "topk.wait"]


def test_the_mesh_layout_takes_the_dense_mask_and_answers_the_same(
        shop, monkeypatch):
    """A model whose catalog is sharded over a serving mesh hands its
    kernel the dense host mask (path ``dense``), observed from the layout:
    same rules, same answer as the flat layout's rows."""
    import dataclasses

    from incubator_predictionio_tpu.parallel.mesh import mesh_from_devices

    monkeypatch.setattr(telemetry._STATE, "metrics_on", True)
    sharded = dataclasses.replace(
        shop.model, serving_mesh=mesh_from_devices(devices=jax.devices()[:4]),
        _sharded_cat=None, _storage=shop.storage)
    assert (sharded.catalog().layout, shop.model.catalog().layout) == (
        "mesh", "flat")
    rules = dict(categories=["c0", "c2"], black_list=_top(shop, 1, 3),
                 white_list=[item(j) for j in range(0, N_ITEMS, 3)])
    dense = _filters._M_MASK_PATH.labels("dense").value()
    got = sharded.recommend("u1", 10, **rules)
    assert _filters._M_MASK_PATH.labels("dense").value() == dense + 1
    assert len(got) == 10 and got == shop.model.recommend("u1", 10, **rules)
