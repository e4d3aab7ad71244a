"""NB/LR kernel correctness vs sklearn references on the CPU mesh."""

import numpy as np

from incubator_predictionio_tpu.ops.linear import (
    train_logistic_regression,
    train_naive_bayes,
)
from incubator_predictionio_tpu.ops.llr import llr_scores
import jax.numpy as jnp


def _toy_counts(n=300, d=12, c=3, seed=0):
    rng = np.random.default_rng(seed)
    y = rng.integers(0, c, n)
    centers = rng.random((c, d)) * 5
    x = rng.poisson(centers[y]).astype(np.float32)
    return x, y.astype(np.int32), c


def test_naive_bayes_matches_sklearn():
    from sklearn.naive_bayes import MultinomialNB

    x, y, c = _toy_counts()
    model = train_naive_bayes(x, y, c, smoothing=1.0)
    ref = MultinomialNB(alpha=1.0).fit(x, y)
    np.testing.assert_allclose(model.log_prior, ref.class_log_prior_, rtol=1e-5)
    np.testing.assert_allclose(
        model.log_likelihood, ref.feature_log_prob_, rtol=1e-4, atol=1e-5
    )
    pred = np.argmax(model.predict_log_joint(x), axis=1)
    assert (pred == ref.predict(x)).mean() > 0.999


def test_logistic_regression_learns():
    rng = np.random.default_rng(1)
    n, d = 400, 6
    w_true = rng.standard_normal((d, 3))
    x = rng.standard_normal((n, d)).astype(np.float32)
    y = np.argmax(x @ w_true + 0.1 * rng.standard_normal((n, 3)), axis=1).astype(np.int32)
    model = train_logistic_regression(x, y, 3, reg=1e-4, max_iters=80)
    acc = (np.argmax(model.predict_logits(x), axis=1) == y).mean()
    assert acc > 0.95, f"LR underfit, acc={acc}"
    # probabilities normalized
    p = model.predict_proba(x[:5])
    np.testing.assert_allclose(p.sum(axis=1), 1.0, rtol=1e-5)


def test_logistic_regression_matches_sklearn_direction():
    from sklearn.linear_model import LogisticRegression

    rng = np.random.default_rng(2)
    x = rng.standard_normal((300, 4)).astype(np.float32)
    y = (x @ np.array([1.0, -2.0, 0.5, 0.0]) > 0).astype(np.int32)
    ours = train_logistic_regression(x, y, 2, reg=1e-2, max_iters=100)
    ref = LogisticRegression(C=1.0 / (300 * 1e-2), fit_intercept=True).fit(x, y)
    ours_w = ours.weights[:, 1] - ours.weights[:, 0]
    cos = np.dot(ours_w, ref.coef_[0]) / (
        np.linalg.norm(ours_w) * np.linalg.norm(ref.coef_[0])
    )
    assert cos > 0.999, f"weight direction mismatch, cos={cos}"


def test_llr_scores_known_values():
    """Dunning G² sanity: independence → 0, strong association → large."""
    # perfectly independent 2x2: k11=25 k12=25 k21=25 k22=25
    z = llr_scores(jnp.float32(25), jnp.float32(25), jnp.float32(25), jnp.float32(25))
    assert float(z) < 1e-3
    # strong association
    s = llr_scores(jnp.float32(50), jnp.float32(5), jnp.float32(5), jnp.float32(1000))
    assert float(s) > 100
    # scipy cross-check: G-test statistic
    from scipy.stats import chi2_contingency

    table = np.array([[13.0, 7.0], [4.0, 76.0]])
    g, _, _, _ = chi2_contingency(table, correction=False, lambda_="log-likelihood")
    ours = llr_scores(*[jnp.float32(v) for v in table.flatten()])
    np.testing.assert_allclose(float(ours), g, rtol=1e-5)


def test_e2_helpers():
    from incubator_predictionio_tpu.e2.engine import (
        BinaryVectorizer,
        CategoricalNaiveBayes,
        markov_chain,
    )
    import numpy as _np

    points = [("spam", ["win", "now"]), ("spam", ["win", "cash"]),
              ("ham", ["hello", "friend"]), ("ham", ["hello", "now"])]
    model = CategoricalNaiveBayes.train(points)
    assert model.predict(["win", "cash"]) == "spam"
    assert model.predict(["hello", "friend"]) == "ham"

    vec = BinaryVectorizer.fit(f for _, f in points)
    x = vec.transform(["win", "now"])
    assert x.sum() == 2 and x.shape[0] == vec.n_features
    assert vec.transform(["unknown", "unknown"]).sum() == 0

    chain = markov_chain(_np.array([[0, 3, 1], [2, 0, 0], [0, 0, 0]]), top_k=2)
    assert chain[0][0] == (1, 0.75)
    assert chain[2] == []


def test_llr_contingency_uses_distinct_users():
    """Review fix: marginals must be distinct-user counts (Mahout
    semantics), verified against a hand-computed contingency table."""
    from incubator_predictionio_tpu.ops.llr import cco_indicators
    from scipy.stats import chi2_contingency

    # 10 users; 4 bought i0, of which 3 viewed i1; 2 more viewed i1 only.
    pu = np.array([0, 1, 2, 3]); pi = np.zeros(4, np.int32)
    su = np.array([0, 1, 2, 4, 5]); si = np.ones(5, np.int32)
    ind = cco_indicators(pu, pi, su, si, n_users=10, n_items=2,
                         max_correlators=2, u_chunk=4)
    # contingency: k11=3 (bought i0 & viewed i1), k12=1, k21=2, k22=4
    g, _, _, _ = chi2_contingency(
        np.array([[3.0, 1.0], [2.0, 4.0]]), correction=False,
        lambda_="log-likelihood",
    )
    slot = list(ind.idx[0]).index(1)
    np.testing.assert_allclose(ind.score[0, slot], g, rtol=1e-4)


def _dense_llr_reference(pu, pi, su, si, n_users, n_items):
    A = np.zeros((n_users, n_items)); A[pu, pi] = 1
    B = np.zeros((n_users, n_items)); B[su, si] = 1
    C = A.T @ B
    ni, nj, N = A.sum(0), B.sum(0), float(n_users)

    def xlogx(x):
        return np.where(x > 0, x * np.log(np.maximum(x, 1e-30)), 0.0)

    def ent2(a, b):
        return xlogx(a + b) - xlogx(a) - xlogx(b)

    k11 = C
    k12 = np.maximum(ni[:, None] - C, 0)
    k21 = np.maximum(nj[None, :] - C, 0)
    k22 = np.maximum(N - k11 - k12 - k21, 0)
    llr = np.maximum(
        2 * (ent2(k11 + k12, k21 + k22) + ent2(k11 + k21, k12 + k22)
             - (xlogx(k11 + k12 + k21 + k22) - xlogx(k11) - xlogx(k12)
                - xlogx(k21) - xlogx(k22))), 0.0)
    llr = np.where(C > 0, llr, 0.0)
    np.fill_diagonal(llr, 0.0)
    return llr


def test_cco_striped_matches_dense_reference():
    """Item-axis striping + ragged last stripe must reproduce the dense
    LLR matrix exactly (top-k score sets compared per item)."""
    from incubator_predictionio_tpu.ops.llr import cco_indicators

    rng = np.random.default_rng(3)
    n_users, n_items, nnz = 150, 90, 2500
    pu = rng.integers(0, n_users, nnz).astype(np.int32)
    pi = rng.integers(0, n_items, nnz).astype(np.int32)
    su = rng.integers(0, n_users, nnz).astype(np.int32)
    si = rng.integers(0, n_items, nnz).astype(np.int32)
    llr = _dense_llr_reference(pu, pi, su, si, n_users, n_items)
    for blk in (90, 64):  # exact fit and ragged last stripe
        ind = cco_indicators(pu, pi, su, si, n_users, n_items,
                             max_correlators=5, u_chunk=32, item_block=blk)
        for i in range(n_items):
            exp = np.sort(llr[i])[::-1][:5]
            got = np.sort(np.where(ind.idx[i] >= 0, ind.score[i], 0))[::-1][:5]
            n = int((exp > 0).sum())
            np.testing.assert_allclose(got[:n], exp[:n], atol=1e-2)


def test_cco_heavy_user_extraction_is_exact():
    """Bot users (far above mean activity) are routed through the
    rank-renumbered heavy path; results must still match the dense
    reference, and out-of-range item/user ids are dropped. The catalog
    must be large enough that a bot's distinct-item count can exceed the
    heavy_cap floor of 256 — assert the branch actually triggers."""
    from incubator_predictionio_tpu.ops import llr as L

    rng = np.random.default_rng(7)
    n_users, n_items = 200, 400
    pu = rng.integers(0, n_users, 2000).astype(np.int32)
    pi = rng.integers(0, n_items, 2000).astype(np.int32)
    for bot in (5, 50, 199):
        pu = np.concatenate([pu, np.full(900, bot, np.int32)])
        pi = np.concatenate([pi, rng.integers(0, n_items, 900).astype(np.int32)])
    su, si = pu[::-1].copy(), ((pi + 3) % n_items)[::-1].copy()
    llr = _dense_llr_reference(pu, pi, su, si, n_users, n_items)

    # the heavy branch must actually trigger for this data: replicate
    # cco_indicators' cap computation on deduped pairs
    key_p = np.unique(pu.astype(np.int64) * n_items + pi)
    key_s = np.unique(su.astype(np.int64) * n_items + si)
    cp = np.bincount((key_p // n_items).astype(int), minlength=n_users)
    cs = np.bincount((key_s // n_items).astype(int), minlength=n_users)
    cap = max(int(16 * max((cp + cs).sum() / n_users, 1.0)), 256)
    assert ((cp + cs) > cap).any(), "test data no longer triggers heavy path"

    # out-of-range ids must be ignored, not aliased into other pairs
    pu_bad = np.concatenate([pu, [3, 4, n_users + 7]]).astype(np.int32)
    pi_bad = np.concatenate([pi, [-1, n_items, 2]]).astype(np.int32)

    ind = L.cco_indicators(pu_bad, pi_bad, su, si, n_users, n_items,
                           max_correlators=6, u_chunk=32, item_block=64)
    for i in range(n_items):
        exp = np.sort(llr[i])[::-1][:6]
        got = np.sort(np.where(ind.idx[i] >= 0, ind.score[i], 0))[::-1][:6]
        n = int((exp > 0).sum())
        np.testing.assert_allclose(got[:min(n, 6)], exp[:min(n, 6)], atol=1e-2)


import pytest


def _ur_case(name):
    """(indicators, history rows an event type, boost, exclude, k) of one
    case of the scorer's test below."""
    from incubator_predictionio_tpu.ops.llr import Indicators

    if name == "boost_before_topk":
        # Review fix: bias>0 field boosts must influence selection.
        ind = Indicators(idx=np.array([[1], [1], [1]], np.int32),
                         score=np.array([[5.0], [4.0], [3.0]], np.float32))
        return ({"buy": ind}, {"buy": [1]},
                np.array([1.0, 1.0, 10.0], np.float32), None, 1)
    rng = np.random.default_rng(17)
    n_items = 101
    inds = {e: Indicators(
        idx=rng.integers(-1, n_items, size=(n_items, kc)).astype(np.int32),
        score=rng.random((n_items, kc)).astype(np.float32))
        for e, kc in (("buy", 6), ("view", 3))}
    history = {e: np.flatnonzero(rng.random(n_items) < 0.3) for e in inds}
    boost = (np.where(rng.random(n_items) < 0.1, 2.0, 1.0).astype(np.float32)
             if "boosted" in name else None)
    exclude = (rng.random(n_items) < 0.2) if "excluded" in name else None
    return inds, history, boost, exclude, 10


@pytest.mark.parametrize("case", ["boost_before_topk", "plain", "excluded",
                                  "excluded+boosted", "excluded_as_rows"])
def test_ur_score_rows_matches_plain_reference(case):
    """The one scorer a Universal Recommender query runs (URModel calls
    it on its resident indicators): two event types of different widths,
    empty (-1) correlator slots, a business-rule mask (dense, or as rows)
    and a per-item boost applied BEFORE the selection, against the
    definition written out in float64."""
    import jax

    from incubator_predictionio_tpu.ops.llr import (
        place_indicators, score_rows,
    )
    from incubator_predictionio_tpu.ops.topk import RowExclude

    inds, history, boost, exclude, k = _ur_case(case)
    n_items = next(iter(inds.values())).idx.shape[0]
    given = exclude
    if case == "excluded_as_rows":
        given = RowExclude(None, np.flatnonzero(exclude).astype(np.int32),
                           None)
    scores, idx, postings = score_rows(
        place_indicators(inds), history, k, exclude=given,
        boost=None if boost is None else jax.device_put(boost))
    want = np.zeros(n_items)
    hits = 0
    for e, ind in inds.items():
        member = np.zeros(n_items + 1)
        member[np.asarray(history[e], int)] = 1.0
        hit = np.where(ind.idx >= 0, member[ind.idx], 0.0)
        hits += int(hit.sum())
        want += (ind.score.astype(np.float64) * hit).sum(axis=1)
    if boost is not None:
        want *= boost
    if exclude is not None:
        want[exclude] = -np.inf
    order = np.argsort(-want, kind="stable")[:k]
    assert postings == hits
    np.testing.assert_array_equal(np.asarray(idx), order)
    np.testing.assert_allclose(np.asarray(scores), want[order], rtol=1e-5)
    if exclude is not None:
        assert not exclude[np.asarray(idx)].any()


@pytest.mark.parametrize("use_native", [True, False])
def test_fit_tf_coo_native_and_fallback_parity(use_native):
    """Both COO producers (C++ and the Python fallback) must emit the
    identical (doc_ptr, feat, counts, idf) for the same corpus."""
    from incubator_predictionio_tpu.ops.tfidf import TfIdfVectorizer

    docs = ["Hello world hello", "foo BAR foo foo", "", "a b c a",
            "\u00dcn\u00efcode test \u00fcn\u00efcode"]
    ref = TfIdfVectorizer(n_features=64, ngram=2)
    r_ref = ref.fit_tf_coo(docs)
    try:
        v = TfIdfVectorizer(n_features=64, ngram=2)
        r = v.fit_tf_coo(docs, use_native=use_native)
    except Exception as e:
        if use_native and type(e).__name__ == "NativeUnavailable":
            pytest.skip("no native toolchain")
        raise
    for a, b in zip(r_ref, r):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(ref.idf, v.idf)


def test_naive_bayes_coo_matches_dense():
    """The COO path (tokenizer pairs -> device scatter-add) must produce
    the same model as the dense einsum path, through the REAL text
    pipeline (fit_tf vs fit_tf_coo on the same corpus), including the
    folded idf column scale."""
    from incubator_predictionio_tpu.ops.linear import (
        train_naive_bayes, train_naive_bayes_coo,
    )
    from incubator_predictionio_tpu.ops.tfidf import TfIdfVectorizer

    rng = np.random.default_rng(7)
    vocab = [f"tok{i}" for i in range(300)]
    docs, labels = [], []
    for d in range(400):
        c = d % 5
        words = [vocab[(7 * k + 31 * c) % 300]
                 for k in range(int(20 + 60 * rng.random()))]
        docs.append(" ".join(words))
        labels.append(c)
    docs.append("")  # empty doc: counts toward the prior, no features
    labels.append(2)
    labels = np.asarray(labels, np.int32)

    v1 = TfIdfVectorizer(n_features=128)
    dense = v1.fit_tf(docs)
    m_dense = train_naive_bayes(dense, labels, 5, smoothing=1.0,
                                col_scale=v1.idf)

    v2 = TfIdfVectorizer(n_features=128)
    doc_ptr, feat, cnt = v2.fit_tf_coo(docs)
    m_coo = train_naive_bayes_coo(doc_ptr, feat, cnt, labels,
                                  n_classes=5, n_features=128,
                                  smoothing=1.0, col_scale=v2.idf)

    np.testing.assert_allclose(m_coo.log_prior, m_dense.log_prior,
                               rtol=1e-6)
    np.testing.assert_allclose(m_coo.log_likelihood,
                               m_dense.log_likelihood,
                               rtol=1e-5, atol=1e-6)


def test_text_prepared_data_dense_tf_roundtrip():
    """LR's on-demand densification of the preparator's COO equals the
    dense fit exactly."""
    from incubator_predictionio_tpu.models.text_classification import (
        TextPreparator, TrainingData,
    )
    from incubator_predictionio_tpu.ops.tfidf import TfIdfVectorizer

    docs = ["alpha beta beta gamma", "delta alpha", "", "beta beta beta"]
    td = TrainingData(docs, np.zeros(4, np.int32), np.array(["a"]))
    pd = TextPreparator().prepare(None, td)
    assert pd.coo is not None and pd.features is None
    ref = TfIdfVectorizer(n_features=pd.vectorizer.n_features).fit_tf(docs)
    np.testing.assert_array_equal(pd.dense_tf(), ref)
