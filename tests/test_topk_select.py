"""The exact two-stage selection of ops/topk.py against ``jax.lax.top_k``.

``select_topk`` (block maxima, the k best blocks, a sort of their k·L
candidates) must return what ``lax.top_k`` returns on the same row, values
AND indices bit for bit, tie order included; ``select_block_len`` decides
from (n_items, k) alone whether ``_topk_scores`` runs it, and
``pio_topk_select_total{path}`` says which way each call went.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from incubator_predictionio_tpu.common import telemetry  # noqa: E402
from incubator_predictionio_tpu.ops import topk  # noqa: E402

_select = jax.jit(topk.select_topk, static_argnames=("k", "block_len"))


def _random(n, seed=0):
    return np.random.default_rng(seed).normal(size=n).astype(np.float32)


def _few_values(n, seed=1):
    """Three distinct scores: every answer is decided by tie order, and
    the ties run across every block."""
    return np.random.default_rng(seed).integers(0, 3, n).astype(np.float32)


def _tie_at_kth_block(n, block_len, k):
    """k + 3 blocks share one maximum, so the k-th chosen block ties with
    blocks left out; the k - 1 best elements sit in LATER blocks than
    those."""
    row = _random(n, 2).clip(-1.0, 1.0)
    n_blocks = -(-n // block_len)
    tied = np.arange(0, min(k + 3, n_blocks)) * block_len + 5
    row[tied] = 2.0
    best = (n_blocks - 1 - np.arange(max(k - 1, 0))) * block_len
    row[best[best >= 0]] = 3.0
    return row


def _mostly_excluded(n, finite):
    """What ``exclude`` leaves when it suppresses all but ``finite``
    items: fewer finite scores than k."""
    row = np.full(n, -np.inf, np.float32)
    keep = np.random.default_rng(3).choice(n, finite, replace=False)
    row[keep] = _random(finite, 4)
    return row


def _block_excluded(n, block_len):
    """A whole block at -inf (the one that held the best score), and the
    padded tail of the last block beside real -inf rows."""
    row = _random(n, 5)
    row[block_len:2 * block_len] = -np.inf
    row[-3:] = -np.inf
    return row


#: id -> (row, k, block_len): the helper on a given row
ROW_CASES = {
    "random-L128-n-not-multiple": (_random(5000), 10, 128),
    "random-L1024-n-not-multiple": (_random(5000), 10, 1024),
    "random-L128-n-multiple": (_random(4096), 10, 128),
    "ties-over-blocks-L128": (_few_values(5000), 37, 128),
    "ties-over-blocks-L1024": (_few_values(5000), 37, 1024),
    "all-equal-L128": (np.ones(1000, np.float32), 9, 128),
    "tie-at-kth-block-L128": (_tie_at_kth_block(5000, 128, 10), 10, 128),
    "tie-at-kth-block-L1024": (_tie_at_kth_block(9000, 1024, 4), 4, 1024),
    "fewer-than-k-finite-L128": (_mostly_excluded(5000, 3), 10, 128),
    "fewer-than-k-finite-L1024": (_mostly_excluded(5000, 3), 10, 1024),
    "all-excluded-L128": (np.full(1000, -np.inf, np.float32), 10, 128),
    "whole-block-excluded-L128": (_block_excluded(5000, 128), 10, 128),
    "whole-block-excluded-L1024": (_block_excluded(5000, 1024), 10, 1024),
    "k1-L128": (_random(5000, 6), 1, 128),
    "k1-L1024": (_random(5000, 6), 1, 1024),
    "k-equals-L-L128": (_random(5000, 7), 128, 128),
    "k-over-L-L128": (_few_values(5000, 8), 131, 128),
    "k-over-L-L1024": (_random(5000, 9), 1027, 1024),
    "k-equals-n-L128": (_few_values(1000, 10), 1000, 128),
    "k-equals-n-L1024": (_random(1500, 11), 1500, 1024),
    "one-block-L1024": (_random(131, 12), 7, 1024),
}

#: id -> (n_items, rank, k, excluded share, expected path): the shape rule,
#: through top_k_items and the counter
SHAPE_CASES = {
    "shape-1003x16-direct": (1003, 16, 10, 0.0, "direct"),
    "shape-300000x8-blocks": (300_000, 8, 10, 0.0, "blocks"),
    "shape-300000x8-exclude-blocks": (300_000, 8, 4, 0.5, "blocks"),
    "shape-300000x8-k-large-direct": (300_000, 8, 2000, 0.0, "direct"),
}


def _selected(path: str) -> float:
    return topk._M_SELECT.labels(path).value()


def _assert_same(got, want):
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))


@pytest.mark.parametrize("case", [*ROW_CASES, *SHAPE_CASES])
def test_selection_is_lax_top_k_bit_for_bit(case, monkeypatch):
    if case in ROW_CASES:
        row, k, block_len = ROW_CASES[case]
        _assert_same(_select(row, k=k, block_len=block_len),
                     jax.lax.top_k(row, k))
        return
    n_items, rank, k, excluded, path = SHAPE_CASES[case]
    assert ("blocks" if topk.select_block_len(n_items, k) else "direct") \
        == path
    rng = np.random.default_rng(n_items + k)
    items = rng.normal(size=(n_items, rank)).astype(np.float32)
    user = rng.normal(size=rank).astype(np.float32)
    exclude = (rng.random(n_items) < excluded) if excluded else None
    monkeypatch.setattr(telemetry._STATE, "metrics_on", True)
    before = {p: _selected(p) for p in ("blocks", "direct")}
    got = topk.top_k_items(user, items, k, exclude=exclude)
    other = "direct" if path == "blocks" else "blocks"
    assert _selected(path) == before[path] + 1
    assert _selected(other) == before[other]
    tagged = [s for s in telemetry.spans_snapshot()
              if s.name == "topk.dispatch"][-1]
    assert tagged.tags == {"select": path}
    # the reference: the same jitted scoring with lax.top_k of the whole
    # row, as every catalog had it before the selection existed
    monkeypatch.setattr(topk, "select_block_len", lambda n, k: 0)
    direct = jax.jit(topk._topk_scores.__wrapped__, static_argnames=("k",))
    mask = np.zeros(n_items, bool) if exclude is None else exclude
    _assert_same(got, direct(user, items, mask, k=k))
    if exclude is not None:
        assert not exclude[got[1]].any()


@pytest.mark.parametrize("n_items,k,want", [
    (9_400_000, 10, 1024),   # the benchmark's catalog: 9,180 + 10,240
    (9_400_000, 4, 2048),
    (1_000_000, 10, 256),
    (26_744, 10, 128),       # ML-20M's catalog: 209 + 1,280 of 26,744
    (1003, 10, 0),           # B + k·L over half the row: lax.top_k
    (300_000, 2000, 0),
    (255, 1, 0),
    (0, 0, 0),
])
def test_block_len_follows_the_shape(n_items, k, want):
    got = topk.select_block_len(n_items, k)
    assert got == want
    if got:
        def sorted_values(length):
            return -(-n_items // length) + k * length

        assert 2 * sorted_values(got) < n_items
        # no other power of two from 128 up sorts fewer values
        assert sorted_values(got) == min(
            sorted_values(1 << e) for e in range(7, 25))
