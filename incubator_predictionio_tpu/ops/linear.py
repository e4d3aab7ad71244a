"""Linear-model kernels: multinomial Naive Bayes + logistic regression.

The reference's classification templates call MLlib NaiveBayes /
LogisticRegressionWithLBFGS (reference: examples/scala-parallel-
classification, SURVEY.md §2.8 row 2; the distributed treeAggregate of
sufficient stats / gradients lives inside MLlib). TPU-native design:

- NB sufficient stats are one [C,N]×[N,D] matmul (one-hot labelsᵀ ×
  features) — examples row-sharded over the mesh data axis, XLA emits the
  psum over ICI from the sharding annotations (pjit, no manual
  collectives).
- LR is full-batch L-BFGS (optax) with the loss/grad pjit'd the same
  way: per-device partial sums, psum'd gradients — the moral equivalent
  of MLlib's treeAggregate pass, minus the shuffle.

Numerical parity notes (SURVEY.md §7 hard parts): NB smoothing is MLlib's
additive `lambda` (default 1.0); LR matches the template's L2-regularized
multinomial softmax with intercept (regParam applied to weights only).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel.mesh import DATA_AXIS, default_mesh, fast_put, pad_rows
from ..workflow.input_pipeline import (
    PipelineConfig, PipelineStats, chunk_ranges, prefetch, run_pipeline,
)


def _narrow_wire(x: np.ndarray, on_tpu: bool):
    """Narrowest LOSSLESS wire dtype for a feature block: small nonneg
    integer counts fit uint8 (a quarter of the f32 bytes); anything
    bf16-exact still halves them. Only on an accelerator — there is no
    transfer to shrink on the CPU backend, just cast overhead. The
    device side widens back to f32 BEFORE any math, so results are
    bit-identical to an f32 upload."""
    if not on_tpu:
        return x
    x_int = x.astype(np.uint8)
    if np.array_equal(x_int.astype(np.float32), x):
        return x_int
    xb = x.astype(jnp.bfloat16)
    if np.array_equal(xb.astype(np.float32), x):
        return xb
    return x


# ---------------------------------------------------------------------------
# Naive Bayes (multinomial, additive smoothing)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class NaiveBayesModel:
    log_prior: np.ndarray  # [C]
    log_likelihood: np.ndarray  # [C, D]
    n_classes: int
    # Sufficient statistics, carried so the streaming fold-in
    # (workflow/online.py) can fold new labeled examples in EXACTLY —
    # NB's log params are a pure function of (feat, counts, smoothing),
    # so counts + increments == a full retrain on old∪new. None on
    # models persisted before these fields existed (fold-in then
    # declines and asks for one retrain) and on col_scale (TF-IDF)
    # trainings, where the scale itself shifts with new documents.
    feat_counts: Optional[np.ndarray] = None   # [C, D] pre-smoothing
    class_counts: Optional[np.ndarray] = None  # [C]
    smoothing: float = 1.0

    def predict_log_joint(self, x: np.ndarray) -> np.ndarray:
        return x @ self.log_likelihood.T + self.log_prior  # [B, C]


def nb_model_from_counts(feat: np.ndarray, counts: np.ndarray,
                         n_classes: int, smoothing: float,
                         keep_counts: bool = True) -> NaiveBayesModel:
    """(class-feature sums, class counts) → NaiveBayesModel. THE one
    construction every NB trainer and the fold-in path share, so the
    smoothing/normalization math cannot drift between them."""
    # arithmetic runs in the CALLER's dtype (f32 device stats, f64
    # bincounts) so this refactor is bit-identical to the construction
    # it replaced in each trainer
    total = counts.sum()
    log_prior = np.log((counts + 1e-12) / max(total, 1e-12))
    num = feat + smoothing
    log_likelihood = np.log(num) - np.log(num.sum(axis=1, keepdims=True))
    return NaiveBayesModel(
        log_prior=log_prior.astype(np.float32),
        log_likelihood=log_likelihood.astype(np.float32),
        n_classes=n_classes,
        feat_counts=(np.asarray(feat, np.float32)
                     if keep_counts else None),
        class_counts=(np.asarray(counts, np.float32)
                      if keep_counts else None),
        smoothing=float(smoothing),
    )


def nb_fold_in(model: NaiveBayesModel, x: np.ndarray, y: np.ndarray,
               x_remove=None, y_remove=None) -> Optional[NaiveBayesModel]:
    """Exact incremental NB update: add the new examples' sufficient
    statistics (and SUBTRACT ``x_remove``/``y_remove`` — the previous
    example of an entity being re-labeled, so an update replaces
    instead of double-counting) and rebuild the log params — bit-for-
    bit what a retrain on the updated example set would produce
    (integer-count features sum exactly in f32). Returns None when the
    model carries no stored counts (legacy blob or col-scaled
    training): the caller logs and waits for a retrain. Never mutates
    ``model``."""
    feat = getattr(model, "feat_counts", None)
    counts = getattr(model, "class_counts", None)
    if feat is None or counts is None:
        return None
    x = np.asarray(x, np.float32)
    y = np.asarray(y, np.int64)
    if x.ndim != 2 or x.shape[1] != feat.shape[1] or len(x) != len(y):
        return None

    def stats(xs, ys):
        onehot = np.zeros((len(ys), model.n_classes), np.float32)
        onehot[np.arange(len(ys)), ys] = 1.0
        return onehot.T @ xs, onehot.sum(axis=0)

    f_add, c_add = stats(x, y)
    feat = feat + f_add
    counts = counts + c_add
    if x_remove is not None and len(x_remove):
        f_sub, c_sub = stats(np.asarray(x_remove, np.float32),
                             np.asarray(y_remove, np.int64))
        # clip: a corrupt removal record must never drive counts
        # negative (log of a negative smoothed count is NaN)
        feat = np.maximum(feat - f_sub, 0.0)
        counts = np.maximum(counts - c_sub, 0.0)
    return nb_model_from_counts(
        feat, counts, model.n_classes, getattr(model, "smoothing", 1.0))


def _nb_stats_body(x, y, w, n_classes: int):
    # x may arrive bfloat16 or uint8 (lossless narrow uploads, see
    # train_naive_bayes); integer wire dtypes widen to bf16 here so the
    # one-hot einsum feeds the MXU natively, accumulating in float32
    # either way.
    if jnp.issubdtype(x.dtype, jnp.integer):
        x = x.astype(jnp.bfloat16)
    onehot = jax.nn.one_hot(y, n_classes, dtype=x.dtype) * w[:, None].astype(x.dtype)
    feat = jnp.einsum("nc,nd->cd", onehot, x,
                      preferred_element_type=jnp.float32)  # [C, D]
    counts = onehot.astype(jnp.float32).sum(axis=0)  # [C]
    return feat, counts


_nb_stats = functools.partial(jax.jit, static_argnames=("n_classes",))(
    _nb_stats_body)


@functools.partial(jax.jit, static_argnames=("n_classes",),
                   donate_argnums=(0, 1))
def _nb_stats_acc(feat_acc, counts_acc, x, y, w, n_classes: int):
    """One streamed chunk folded into the running [C,D]/[C] stats.
    Accumulators are donated so the ring's steady-state HBM is the
    in-flight chunks plus ONE accumulator. Zero-weight pad rows add
    exact zeros; with count-valued features every partial sum is an
    integer exactly representable in f32, so the chunked reduction
    matches the single-shot einsum bit-for-bit."""
    feat, counts = _nb_stats_body(x, y, w, n_classes)
    # third output: a tiny NON-donated per-chunk value — the ring blocks
    # on it as its completion token (the accumulators themselves are
    # donated into the NEXT step before the ring ever waits on them)
    return feat_acc + feat, counts_acc + counts, counts


def _stream_nb_dense(x, y, n_classes, mesh, on_tpu,
                     cfg: PipelineConfig, stats: Optional[PipelineStats]):
    """Double-buffered featurize→upload→accumulate over row chunks.
    Returns host (feat [C,D], counts [C]) identical to the single-shot
    path (see _nb_stats_acc exactness note)."""
    n_dev = int(np.prod(list(mesh.shape.values())))
    n = x.shape[0]
    # fixed chunk geometry: one compiled program per wire dtype
    step = max(n_dev, -(-min(cfg.chunk_rows, n) // n_dev) * n_dev)
    shard2 = NamedSharding(mesh, P(DATA_AXIS, None))
    shard1 = NamedSharding(mesh, P(DATA_AXIS))

    def featurize(rng):
        s, e = rng
        # per-chunk narrowing: each chunk ships its own narrowest
        # lossless dtype (a late non-uint8 chunk costs one extra
        # compile, never correctness)
        xc = pad_rows(_narrow_wire(x[s:e], on_tpu), step)
        yc = pad_rows(y[s:e], step)
        wc = pad_rows(np.ones(e - s, np.float32), step)  # pad w=0: no-op rows
        return xc, yc, wc

    def upload(chunk):
        xc, yc, wc = chunk
        return (fast_put(xc, shard2), fast_put(yc, shard1),
                fast_put(wc, shard1))

    acc = (jnp.zeros((n_classes, x.shape[1]), jnp.float32),
           jnp.zeros((n_classes,), jnp.float32))

    def consume(dev):
        nonlocal acc
        feat_acc, counts_acc, ready = _nb_stats_acc(
            acc[0], acc[1], *dev, n_classes)
        acc = (feat_acc, counts_acc)
        return ready

    chunks = prefetch(chunk_ranges(n, step), featurize,
                      workers=cfg.workers, lookahead=cfg.depth + 1,
                      stats=stats)
    run_pipeline(chunks, upload, consume, depth=cfg.depth, stats=stats)
    return jax.device_get(acc)


def train_naive_bayes(
    x: np.ndarray,
    y: np.ndarray,
    n_classes: int,
    smoothing: float = 1.0,
    mesh: Optional[Mesh] = None,
    col_scale: Optional[np.ndarray] = None,
    pipeline: Optional[PipelineConfig] = None,
    pipeline_stats: Optional[PipelineStats] = None,
) -> NaiveBayesModel:
    """x [N,D] nonneg features, y [N] int labels. Mesh-sharded stats.

    ``col_scale`` [D] applies a per-feature scale (TF-IDF's idf) to the
    CLASS STATS instead of the examples — mathematically the same as
    training on ``x * col_scale`` (the scale commutes with the row
    reduction) without ever materializing that [N,D] product.

    ``pipeline`` (default: env via PipelineConfig.from_env): when the
    input is large enough, the narrowing cast, host→device upload, and
    on-device stats pass run as an overlapped chunk stream
    (workflow/input_pipeline) instead of three serial full-data phases;
    ``mode='off'`` pins the single-shot path. With count-valued
    features (the multinomial NB domain) the two paths are bit-identical
    (exact f32 integer partial sums).
    """
    mesh = mesh or default_mesh()
    n_dev = int(np.prod(list(mesh.shape.values())))
    x = np.asarray(x, np.float32)
    y = np.asarray(y, np.int32)
    on_tpu = mesh.devices.flat[0].platform == "tpu"
    cfg = pipeline or PipelineConfig.from_env()
    if cfg.enabled_for(x.shape[0]):
        feat, counts = _stream_nb_dense(x, y, n_classes, mesh, on_tpu,
                                        cfg, pipeline_stats)
    else:
        # Single-shot fallback: narrow the whole matrix (halve/quarter
        # the host->device bytes when it costs nothing — only on an
        # accelerator, same gate as als.py's compute_dtype "auto"), one
        # put per operand, one stats dispatch.
        x = _narrow_wire(x, on_tpu)
        w = np.ones(x.shape[0], np.float32)
        xp, yp, wp = pad_rows(x, n_dev), pad_rows(y, n_dev), pad_rows(w, n_dev)
        shard2 = NamedSharding(mesh, P(DATA_AXIS, None))
        shard1 = NamedSharding(mesh, P(DATA_AXIS))
        xp = fast_put(xp, shard2)
        yp = fast_put(yp, shard1)
        wp = fast_put(wp, shard1)
        feat, counts = jax.device_get(_nb_stats(xp, yp, wp, n_classes))
    if col_scale is not None:
        feat = feat * np.asarray(col_scale, np.float32)

    # col-scaled (TF-IDF) stats are not fold-in-able: the scale itself
    # moves with new documents, so stored counts would lie
    return nb_model_from_counts(feat, counts, n_classes, smoothing,
                                keep_counts=col_scale is None)


@functools.partial(jax.jit, static_argnames=("n_classes", "n_features"))
def _nb_stats_coo(cls_idx, feat_idx, counts, n_classes: int,
                  n_features: int):
    """[C, D] class-feature sums from COO token entries via one
    scatter-add over the combined (class, feature) index. Padding
    entries carry count 0 (adds nothing to bucket 0)."""
    idx = cls_idx.astype(jnp.int32) * n_features + feat_idx.astype(jnp.int32)
    feat = jnp.zeros((n_classes * n_features,), jnp.float32)
    feat = feat.at[idx].add(counts.astype(jnp.float32))
    return feat.reshape(n_classes, n_features)


@functools.partial(jax.jit, static_argnames=("n_features",),
                   donate_argnums=(0,))
def _nb_stats_coo_acc(acc_flat, cls_idx, feat_idx, counts, n_features: int):
    """One streamed COO entry chunk scatter-added into the running flat
    [C*D] stats (donated). Pad entries carry count 0 — adding +0.0 at
    bucket 0 is an exact no-op — and per-doc term counts are integers,
    so the chunked scatter matches the single-shot one bit-for-bit."""
    idx = cls_idx.astype(jnp.int32) * n_features + feat_idx.astype(jnp.int32)
    new_acc = acc_flat.at[idx].add(counts.astype(jnp.float32))
    # second output: non-donated completion token for the ring (see
    # _nb_stats_acc)
    return new_acc, counts.astype(jnp.float32).sum()


def _narrow_coo_chunk(cls_e, feat_e, cnt_e, n_classes: int, n_features: int):
    """Lossless narrow wire dtypes for one COO entry chunk (widened on
    device): feature ids uint16 when D fits, class ids uint8 when C
    fits, counts uint16 when every count does."""
    if n_features <= np.iinfo(np.uint16).max + 1:
        feat_e = feat_e.astype(np.uint16)
    if n_classes <= np.iinfo(np.uint8).max + 1:
        cls_e = cls_e.astype(np.uint8)
    if cnt_e.size and float(cnt_e.max()) <= np.iinfo(np.uint16).max \
            and np.array_equal(cnt_e.astype(np.uint16), cnt_e):
        cnt_e = cnt_e.astype(np.uint16)
    return cls_e, feat_e, cnt_e


def rebatch_entries(chunks: Iterable[tuple], chunk_entries: int):
    """Re-chunk a ragged stream of (cls, feat, counts) COO entry blocks
    into FIXED-size entry chunks (the last one short) so the device
    consumer compiles one program instead of one per ragged shape.
    Pure host-side carry logic on the consumer thread; entry order is
    preserved exactly."""
    step = max(1, int(chunk_entries))
    carry: list[tuple] = []
    held = 0

    def drain(parts, take):
        out, rest, got = [], [], 0
        for p in parts:
            n = len(p[0])
            if got + n <= take:
                out.append(p)
                got += n
            else:
                k = take - got
                if k > 0:
                    out.append(tuple(a[:k] for a in p))
                    rest.append(tuple(a[k:] for a in p))
                    got = take
                else:
                    rest.append(p)
        cat = tuple(np.concatenate([p[j] for p in out])
                    if len(out) != 1 else out[0][j] for j in range(3))
        return cat, rest

    for block in chunks:
        carry.append(block)
        held += len(block[0])
        while held >= step:
            full, carry = drain(carry, step)
            held -= step
            yield full
    if held:
        last, carry = drain(carry, held)
        yield last


def train_naive_bayes_coo(
    doc_ptr: np.ndarray,
    feat_idx: np.ndarray,
    counts: np.ndarray,
    y: np.ndarray,
    n_classes: int,
    n_features: int,
    smoothing: float = 1.0,
    mesh: Optional[Mesh] = None,
    col_scale: Optional[np.ndarray] = None,
    pipeline: Optional[PipelineConfig] = None,
    pipeline_stats: Optional[PipelineStats] = None,
) -> NaiveBayesModel:
    """NB from the tokenizer's COO output (ops/tfidf.fit_tf_coo): the
    dense [N, D] matrix never exists — only the ~150 distinct buckets
    per doc cross the host->device link (13x fewer bytes at the
    20-newsgroups shape), and the class-feature stats come from one
    device scatter-add. Numerically equivalent to train_naive_bayes on
    the materialized matrix: the per-class sum is the same additions in
    a different association order, both accumulating f32 (tests pin
    near-identity; ulp-level reduction-order differences are possible).

    Uploads narrow where lossless: feature ids as uint16 when D fits,
    class ids as uint8 when C fits, counts as uint16 when all counts do
    (per-doc term frequencies overwhelmingly fit).

    ``pipeline``: when the entry stream is large enough, upload and
    scatter-add run as an overlapped fixed-size chunk stream (see
    train_naive_bayes_coo_stream, which additionally overlaps the
    tokenizer itself when fed from a chunked corpus).
    """
    mesh = mesh or default_mesh()
    n_dev = int(np.prod(list(mesh.shape.values())))
    y = np.asarray(y, np.int32)
    cls_per_entry = np.repeat(y, np.diff(np.asarray(doc_ptr)))
    feat_idx = np.asarray(feat_idx)
    counts = np.asarray(counts, np.float32)

    cfg = pipeline or PipelineConfig.from_env()
    if cfg.enabled_for(len(feat_idx)):
        return train_naive_bayes_coo_stream(
            iter([(cls_per_entry, feat_idx, counts)]), y, n_classes,
            n_features, smoothing=smoothing, mesh=mesh, col_scale=col_scale,
            pipeline=cfg, pipeline_stats=pipeline_stats,
        )

    # lossless narrow uploads (widened on device by _nb_stats_coo)
    cls_up, feat_up, cnt_up = _narrow_coo_chunk(
        cls_per_entry, feat_idx, counts, n_classes, n_features)

    cp = pad_rows(cls_up, n_dev)
    fp = pad_rows(feat_up, n_dev)
    wp = pad_rows(cnt_up, n_dev)      # pad counts are 0: contribute nothing
    shard1 = NamedSharding(mesh, P(DATA_AXIS))
    cp = fast_put(cp, shard1)
    fp = fast_put(fp, shard1)
    wp = fast_put(wp, shard1)
    feat = np.asarray(jax.device_get(
        _nb_stats_coo(cp, fp, wp, n_classes, n_features)))
    return _nb_model_from_stats(feat, y, n_classes, smoothing, col_scale)


def _nb_model_from_stats(feat, y, n_classes, smoothing, col_scale):
    if col_scale is not None:
        feat = feat * np.asarray(col_scale, np.float32)
    class_counts = np.bincount(y, minlength=n_classes).astype(np.float64)
    return nb_model_from_counts(feat, class_counts, n_classes, smoothing,
                                keep_counts=col_scale is None)


def train_naive_bayes_coo_stream(
    entry_blocks: Iterable[tuple],
    y: np.ndarray,
    n_classes: int,
    n_features: int,
    smoothing: float = 1.0,
    mesh: Optional[Mesh] = None,
    col_scale=None,
    pipeline: Optional[PipelineConfig] = None,
    pipeline_stats: Optional[PipelineStats] = None,
) -> NaiveBayesModel:
    """NB from a STREAM of COO entry blocks — the fully overlapped text
    path: tokenizer workers (prefetch over doc chunks) feed ragged
    (cls, feat, counts) blocks, which are rebatched into fixed-size
    entry chunks, uploaded narrow, and scatter-added into the running
    device stats while the next chunk tokenizes. Bit-identical to
    train_naive_bayes_coo on the concatenated stream (same integer
    additions, different association order — exact in f32).

    ``col_scale`` may be a ZERO-ARG CALLABLE evaluated after the stream
    is exhausted: TF-IDF's idf only exists once the last chunk's
    document frequencies are counted.
    """
    mesh = mesh or default_mesh()
    n_dev = int(np.prod(list(mesh.shape.values())))
    y = np.asarray(y, np.int32)
    cfg = pipeline or PipelineConfig.from_env()
    step = max(n_dev, -(-cfg.chunk_rows // n_dev) * n_dev)
    shard1 = NamedSharding(mesh, P(DATA_AXIS))

    def upload(chunk):
        cls_e, feat_e, cnt_e = _narrow_coo_chunk(
            np.asarray(chunk[0]), np.asarray(chunk[1]),
            np.asarray(chunk[2], np.float32), n_classes, n_features)
        return (fast_put(pad_rows(cls_e, step), shard1),
                fast_put(pad_rows(feat_e, step), shard1),
                fast_put(pad_rows(cnt_e, step), shard1))

    acc = jnp.zeros((n_classes * n_features,), jnp.float32)

    def consume(dev):
        nonlocal acc
        acc, ready = _nb_stats_coo_acc(acc, *dev, n_features)
        return ready

    run_pipeline(rebatch_entries(entry_blocks, step), upload, consume,
                 depth=cfg.depth, stats=pipeline_stats)
    feat = np.asarray(jax.device_get(acc)).reshape(n_classes, n_features)
    if callable(col_scale):
        col_scale = col_scale()
    return _nb_model_from_stats(feat, y, n_classes, smoothing, col_scale)


# ---------------------------------------------------------------------------
# Logistic regression (multinomial softmax, L2, L-BFGS)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class LogisticRegressionModel:
    weights: np.ndarray  # [D, C]
    intercept: np.ndarray  # [C]
    n_classes: int

    def predict_logits(self, x: np.ndarray) -> np.ndarray:
        return x @ self.weights + self.intercept

    def predict_proba(self, x: np.ndarray) -> np.ndarray:
        z = self.predict_logits(x)
        z = z - z.max(axis=-1, keepdims=True)
        e = np.exp(z)
        return e / e.sum(axis=-1, keepdims=True)


def lr_sgd_steps(model: LogisticRegressionModel, x: np.ndarray,
                 y: np.ndarray, *, reg: float = 0.0, lr: float = 0.05,
                 epochs: int = 5) -> Optional[LogisticRegressionModel]:
    """Online SGD on a COPY of an LR model: a few full-batch softmax
    cross-entropy gradient steps over the NEW examples only — the
    streaming fold-in update (workflow/online.py). Host numpy on
    purpose: an increment is a handful of examples, and warm serving
    weights only need a nudge toward them, not an L-BFGS re-solve.
    Returns None on shape mismatch (feature count changed: retrain)."""
    x = np.asarray(x, np.float32)
    y = np.asarray(y, np.int64)
    w = np.array(model.weights, np.float32, copy=True)
    b = np.array(model.intercept, np.float32, copy=True)
    if x.ndim != 2 or x.shape[1] != w.shape[0] or len(x) != len(y) \
            or not len(x):
        return None
    onehot = np.zeros((len(y), model.n_classes), np.float32)
    onehot[np.arange(len(y)), y] = 1.0
    for _ in range(max(1, int(epochs))):
        z = x @ w + b
        z -= z.max(axis=1, keepdims=True)
        e = np.exp(z)
        p = e / e.sum(axis=1, keepdims=True)
        g = (p - onehot) / len(y)
        w -= lr * (x.T @ g + reg * w)
        b -= lr * g.sum(axis=0)
    return LogisticRegressionModel(weights=w, intercept=b,
                                   n_classes=model.n_classes)


@functools.partial(jax.jit, static_argnames=("n_classes",),
                   donate_argnums=())
def _lr_fit(xp, yp, maskp, n, reg, tol, max_iters, n_classes: int):
    """The ENTIRE L-BFGS optimization in one jit (lax.while_loop with
    the convergence test on-device). Module-level so the compiled
    executable is REUSED across train calls at the same shapes — a
    per-call closure would retrace+recompile every `pio train`, and a
    host-side step loop would pay a dispatch+readback round trip per
    iteration."""
    import optax

    # narrow wire dtypes (uint8 / lossless bf16) widen back to f32
    # BEFORE any math: results are bit-identical to an f32 upload
    xp = xp.astype(jnp.float32)

    d = xp.shape[1]

    def loss_fn(params):
        w, b = params
        logits = xp @ w + b  # [Np, C] row-sharded
        logp = jax.nn.log_softmax(logits)
        # one-hot contraction, NOT take_along_axis: a per-row gather runs
        # at the TPU gather unit's fixed ~420M rows/s (docs/tpu.md,
        # measured 2026-07) — 6x the cost of this elementwise mask at
        # bench shape.
        onehot = jax.nn.one_hot(yp, n_classes, dtype=logp.dtype)
        nll = -(logp * onehot).sum(axis=1)
        data = jnp.sum(nll * maskp) / n
        return data + 0.5 * reg * jnp.sum(w * w)

    # Backtracking linesearch instead of the default zoom: zoom's
    # while_loop lowers to ~1.7s/step at 2M-example shape (hundreds of
    # serialized loss evals); backtracking converges the template
    # configurations identically at ~3ms/step.
    opt = optax.lbfgs(linesearch=optax.scale_by_backtracking_linesearch(
        max_backtracking_steps=20, store_grad=True))
    value_and_grad = optax.value_and_grad_from_state(loss_fn)

    def step(carry):
        it, params, state, prev, _ = carry
        value, grad = value_and_grad(params, state=state)
        updates, state = opt.update(
            grad, state, params, value=value, grad=grad, value_fn=loss_fn
        )
        params = optax.apply_updates(params, updates)
        # optax.tree.norm is the 0.2.4+ spelling; older optax (this
        # container ships 0.2.3) has the same function as
        # tree_utils.tree_l2_norm
        tree_ns = getattr(optax, "tree", None)
        gnorm = (tree_ns.norm(grad) if tree_ns is not None
                 else optax.tree_utils.tree_l2_norm(grad))
        done = (jnp.abs(prev - value)
                < tol * jnp.maximum(1.0, jnp.abs(prev))) & (gnorm < 1e-4)
        return it + 1, params, state, value, done

    def cond(carry):
        it, _, _, _, done = carry
        return (it < max_iters) & ~done

    params = (jnp.zeros((d, n_classes)), jnp.zeros((n_classes,)))
    carry = (jnp.int32(0), params, opt.init(params), jnp.float32(jnp.inf),
             jnp.bool_(False))
    carry = jax.lax.while_loop(cond, step, carry)
    return carry[1]


@functools.lru_cache(maxsize=8)
def _cached_concat_widen(n_chunks: int, sharding):
    """jit'd on-device assembly of the full row-sharded f32 matrix from
    the streamed chunks (module-cached so warm trains reuse the
    executable). Chunks are donated — XLA reclaims their HBM into the
    result instead of holding both."""
    def cat(*chunks):
        return jnp.concatenate([c.astype(jnp.float32) for c in chunks],
                               axis=0)

    # CPU can't alias into a concatenate — donating there only emits a
    # "donated buffers were not usable" warning per call
    donate = (tuple(range(n_chunks))
              if jax.default_backend() != "cpu" else ())
    return jax.jit(cat, out_shardings=sharding, donate_argnums=donate)


def _stream_lr_upload(x, mesh, on_tpu, cfg: PipelineConfig,
                      stats: Optional[PipelineStats]):
    """Overlapped narrow-cast + upload of the LR feature matrix: workers
    cast chunk N+1 to its narrowest lossless wire dtype while chunk N
    uploads; the sharded full array is then assembled on device. Row
    content (incl. zero pad rows) matches pad_rows(x, n_dev) exactly."""
    n_dev = int(np.prod(list(mesh.shape.values())))
    n = x.shape[0]
    step = max(n_dev, -(-min(cfg.chunk_rows, n) // n_dev) * n_dev)
    shard2 = NamedSharding(mesh, P(DATA_AXIS, None))

    def featurize(rng):
        s, e = rng
        xc = _narrow_wire(x[s:e], on_tpu)
        # only the LAST chunk can be non-divisible: pad it like the
        # single-shot global pad (same total row count, same zeros)
        return pad_rows(xc, n_dev) if (e - s) % n_dev else xc

    dev_chunks = []

    def consume(dev):
        dev_chunks.append(dev)
        return dev

    chunks = prefetch(chunk_ranges(n, step), featurize,
                      workers=cfg.workers, lookahead=cfg.depth + 1,
                      stats=stats)
    run_pipeline(chunks, lambda hc: fast_put(hc, shard2), consume,
                 depth=cfg.depth, stats=stats)
    if len(dev_chunks) == 1:
        return dev_chunks[0]
    return _cached_concat_widen(len(dev_chunks), shard2)(*dev_chunks)


def train_logistic_regression(
    x: np.ndarray,
    y: np.ndarray,
    n_classes: int,
    reg: float = 0.0,
    max_iters: int = 100,
    tol: float = 1e-6,
    mesh: Optional[Mesh] = None,
    pipeline: Optional[PipelineConfig] = None,
    pipeline_stats: Optional[PipelineStats] = None,
) -> LogisticRegressionModel:
    """Full-batch multinomial LR via optax L-BFGS; data row-sharded over
    the mesh, gradient psum inserted by XLA.

    ``pipeline``: L-BFGS needs the whole matrix resident, so the stream
    cannot reduce chunks away like NB — instead the narrowing cast and
    the upload overlap per chunk, and the full sharded [Np, D] array is
    assembled ON DEVICE from the uploaded chunks (one concatenate; the
    chunks are donated into it). The assembled array is bit-identical
    to the single-shot upload, so the fitted model is too.
    """
    mesh = mesh or default_mesh()
    n_dev = int(np.prod(list(mesh.shape.values())))
    x = np.asarray(x, np.float32)
    y = np.asarray(y, np.int32)
    n = x.shape[0]
    on_tpu = mesh.devices.flat[0].platform == "tpu"
    shard2 = NamedSharding(mesh, P(DATA_AXIS, None))
    shard1 = NamedSharding(mesh, P(DATA_AXIS))
    cfg = pipeline or PipelineConfig.from_env()
    if cfg.enabled_for(n):
        xp = _stream_lr_upload(x, mesh, on_tpu, cfg, pipeline_stats)
        yp = fast_put(pad_rows(y, n_dev), shard1)
        maskp = fast_put(pad_rows(np.ones(n, np.float32), n_dev), shard1)
    else:
        # Lossless narrow wire (same gate as train_naive_bayes); _lr_fit
        # widens back to f32 on device FIRST, so the optimization math
        # and its results are bit-identical to an f32 upload.
        x = _narrow_wire(x, on_tpu)
        mask = pad_rows(np.ones(n, np.float32), n_dev)
        xp = fast_put(pad_rows(x, n_dev), shard2)
        yp = fast_put(pad_rows(y, n_dev), shard1)
        maskp = fast_put(mask, shard1)

    params = _lr_fit(xp, yp, maskp, jnp.float32(n), jnp.float32(reg),
                     jnp.float32(tol), jnp.int32(max_iters), n_classes)
    w, b = jax.device_get(params)
    return LogisticRegressionModel(
        weights=np.asarray(w, np.float32),
        intercept=np.asarray(b, np.float32),
        n_classes=n_classes,
    )


# ---------------------------------------------------------------------------
# partition-local (process-sharded) entry points — SparkNet-style
# synchronous data parallelism (arxiv 1511.06051) over the gang mesh
# ---------------------------------------------------------------------------


def _assemble_process_shards(x: np.ndarray, y: np.ndarray,
                             mesh: Mesh):
    """Assemble each gang process's LOCAL example block into global
    row-sharded arrays: rows are padded (mask 0) to the gang-wide
    per-device maximum so every process compiles the identical
    program, then stitched with ``make_array_from_process_local_data``.
    Row ownership is irrelevant — both consumers reduce with psum'd
    sums that zero-mask rows contribute nothing to. Returns
    ``(xp, yp, maskp, n_global)`` with ``n_global`` the gang-wide real
    example count (the loss normalizer).

    No wire narrowing here on purpose: the narrow dtype is a function
    of the LOCAL block, and per-process dtype disagreement would
    compile divergent programs across the gang.
    """
    from jax.experimental import multihost_utils

    n_proc = jax.process_count()
    n_dev = int(np.prod(list(mesh.shape.values())))
    if n_dev % n_proc:
        raise ValueError(
            f"{n_dev} devices do not divide {n_proc} processes")
    local_devs = n_dev // n_proc
    x = np.asarray(x, np.float32)
    y = np.asarray(y, np.int32)
    n_local = x.shape[0]

    def agather(v):
        return np.asarray(
            multihost_utils.process_allgather(
                np.asarray(v, np.int32))).reshape(-1)

    per_dev = int(agather(-(-max(n_local, 1) // local_devs)).max())
    n_global = int(agather(n_local).sum())
    rows_local = per_dev * local_devs

    def pad_block(a):
        out = np.zeros((rows_local,) + a.shape[1:], a.dtype)
        out[:n_local] = a
        return out

    xl = pad_block(x)
    yl = pad_block(y)
    ml = pad_block(np.ones(n_local, np.float32))
    shard2 = NamedSharding(mesh, P(DATA_AXIS, None))
    shard1 = NamedSharding(mesh, P(DATA_AXIS))

    def to_global(a, sh):
        if n_proc == 1:
            return fast_put(a, sh)
        return jax.make_array_from_process_local_data(
            sh, a, (a.shape[0] * n_proc,) + a.shape[1:])

    return (to_global(xl, shard2), to_global(yl, shard1),
            to_global(ml, shard1), n_global)


def train_naive_bayes_process_local(
    x: np.ndarray,
    y: np.ndarray,
    n_classes: int,
    smoothing: float = 1.0,
    mesh: Optional[Mesh] = None,
) -> NaiveBayesModel:
    """NB where each gang process holds only ITS event-log partitions'
    examples (workflow/train_feed.py). Sufficient statistics are pure
    sums, so the psum XLA inserts for the row-sharded one-hot matmul
    IS the cross-partition reduction — the result is exactly the
    single-process model over the union (integer counts sum exactly in
    f32). ``n_classes`` must be the gang-agreed GLOBAL class count
    (the label vocabulary is allgathered by the feed orchestrator)."""
    mesh = mesh or default_mesh()
    if jax.process_count() == 1:
        return train_naive_bayes(x, y, n_classes, smoothing=smoothing,
                                 mesh=mesh)
    xp, yp, wp, _n = _assemble_process_shards(x, y, mesh)
    feat, counts = jax.device_get(_nb_stats(xp, yp, wp, n_classes))
    return nb_model_from_counts(feat, counts, n_classes, smoothing)


def train_logistic_regression_process_local(
    x: np.ndarray,
    y: np.ndarray,
    n_classes: int,
    reg: float = 0.0,
    max_iters: int = 100,
    tol: float = 1e-6,
    mesh: Optional[Mesh] = None,
) -> LogisticRegressionModel:
    """LR over partition-local example blocks: the SAME jitted L-BFGS
    (:func:`_lr_fit`) the single-process path runs — its loss/grad
    sums are row-sharded psums, so feeding each process its own
    partitions' rows (mask-padded to a common shape) yields
    synchronous data-parallel training with gradients all-reduced
    every step (SparkNet, arxiv 1511.06051). The loss normalizer is
    the gang-wide example count."""
    mesh = mesh or default_mesh()
    if jax.process_count() == 1:
        return train_logistic_regression(
            x, y, n_classes, reg=reg, max_iters=max_iters, tol=tol,
            mesh=mesh)
    xp, yp, maskp, n_global = _assemble_process_shards(x, y, mesh)
    params = _lr_fit(xp, yp, maskp, jnp.float32(n_global),
                     jnp.float32(reg), jnp.float32(tol),
                     jnp.int32(max_iters), n_classes)
    w, b = jax.device_get(params)
    return LogisticRegressionModel(
        weights=np.asarray(w, np.float32),
        intercept=np.asarray(b, np.float32),
        n_classes=n_classes,
    )
