"""Text-classification template (TF-IDF + NB / LR).

Reference: predictionio-template-text-classifier (SURVEY.md §2.8 row 4):
"documents" events carry {"text", "label"} properties; tokenize → TF-IDF
→ MLlib NaiveBayes or LogisticRegression; query = raw text → category +
confidence.

Wire format (template parity):
  query  {"text": "I like speed and fast motorcycles."}
  result {"category": "motorcycles", "confidence": 0.87}
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import numpy as np

from ..controller import Algorithm, DataSource, Engine, EngineFactory, Params, SanityCheck
from ..data.store.p_event_store import PEventStore
from ..ops.linear import (
    NaiveBayesModel,
    train_logistic_regression,
    train_naive_bayes,
    train_naive_bayes_coo,
)
from ..ops.tfidf import TfIdfVectorizer
from ..workflow.input_pipeline import pipeline_of as _pipeline_of


@dataclasses.dataclass
class TrainingData(SanityCheck):
    texts: list[str]
    labels: np.ndarray  # [N] int32
    label_values: np.ndarray

    def sanity_check(self):
        assert len(self.texts) > 0, "no documents found"


@dataclasses.dataclass
class PreparedData:
    features: Optional[np.ndarray]  # [N, D] tf-idf / raw tf, or None (COO)
    labels: np.ndarray
    label_values: np.ndarray
    vectorizer: TfIdfVectorizer
    #: features hold RAW term frequencies; the fitted idf column scale
    #: is applied inside the trainer (commutes with NB's stats
    #: reduction — skips materializing the scaled [N,D] matrix)
    features_are_tf: bool = False
    #: COO representation (ops/tfidf.fit_tf_coo): (doc_ptr, feat, cnt).
    #: The preparator emits THIS by default — NB trains straight from
    #: it (device segment-sum; the dense matrix never exists) and the
    #: LR path densifies on demand via dense_tf().
    coo: Optional[tuple] = None
    #: Streaming mode (workflow/input_pipeline): the preparator DEFERS
    #: featurization — coo is None and the raw corpus rides along so the
    #: NB trainer can overlap tokenize/upload/scatter chunk-by-chunk
    #: (TextNBAlgorithm.train). Non-streaming consumers (LR, dense_tf)
    #: fall back to a one-shot fit of the same vectorizer.
    texts: Optional[list] = None

    def ensure_coo(self):
        """Materialize the one-shot COO from a deferred (streaming)
        preparation — the fallback for consumers that need every doc's
        rows at once."""
        if self.coo is None and self.texts is not None:
            self.coo = self.vectorizer.fit_tf_coo(self.texts)
        return self.coo

    def dense_tf(self) -> np.ndarray:
        """Materialize the raw-tf matrix from the COO (LR needs the
        full per-doc rows; NB never calls this)."""
        if self.features is not None:
            return self.features
        doc_ptr, feat, cnt = self.ensure_coo()
        n, d = len(doc_ptr) - 1, self.vectorizer.n_features
        x = np.zeros((n, d), np.float32)
        rows = np.repeat(np.arange(n), np.diff(np.asarray(doc_ptr)))
        x[rows, feat] = cnt
        return x


@dataclasses.dataclass(frozen=True)
class DataSourceParams(Params):
    app_name: str = ""
    event_names: Sequence[str] = ("documents",)
    entity_type: str = "content"
    text_property: str = "text"
    label_property: str = "label"


class TextDataSource(DataSource):
    params_cls = DataSourceParams
    params_aliases = {"appName": "app_name", "eventNames": "event_names"}

    def read_training(self, ctx) -> TrainingData:
        p: DataSourceParams = self.params
        texts, labels = [], []
        # chunked scan: only one chunk's Event objects are ever live
        # alongside the extracted text/label columns
        for batch in PEventStore.find_batches(
                p.app_name or ctx.app_name,
                event_names=list(p.event_names),
                entity_type=p.entity_type,
                storage=ctx.get_storage(),
                channel_name=ctx.channel_name,
        ):
            for props in batch.properties:
                if p.text_property in props and p.label_property in props:
                    texts.append(str(props[p.text_property]))
                    labels.append(props[p.label_property])
        label_values, y = np.unique(np.asarray(labels), return_inverse=True)
        return TrainingData(texts, y.astype(np.int32), label_values)

    def read_eval(self, ctx):
        from ..e2.cross_validation import k_fold_indices

        td = self.read_training(ctx)
        folds = []
        for train_sel, test_sel in k_fold_indices(len(td.texts), k=3, seed=2):
            train = TrainingData(
                [td.texts[j] for j in np.nonzero(train_sel)[0]],
                td.labels[train_sel], td.label_values,
            )
            queries = [
                ({"text": td.texts[j]},
                 {"category": str(td.label_values[td.labels[j]])})
                for j in np.nonzero(test_sel)[0]
            ]
            folds.append((train, None, queries))
        return folds


@dataclasses.dataclass(frozen=True)
class PreparatorParams(Params):
    n_features: int = 4096
    ngram: int = 1


class TextPreparator:
    """TF-IDF fit (reference: template's Preparator builds the
    HashingTF/IDF transform)."""

    params_cls = PreparatorParams
    params_aliases = {"numFeatures": "n_features", "nGram": "ngram"}

    def __init__(self, params=None):
        self.params = params or PreparatorParams()

    def prepare(self, ctx, td: TrainingData) -> PreparedData:
        vec = TfIdfVectorizer(
            n_features=self.params.n_features, ngram=self.params.ngram
        )
        cfg = _pipeline_of(ctx)
        if cfg is not None and cfg.enabled_for(len(td.texts),
                                               chunk=cfg.chunk_docs):
            # Defer featurization into the training stream: tokenizing
            # here would serialize the dominant host cost of this
            # template in front of upload + compute (the exact stall the
            # input pipeline exists to remove).
            return PreparedData(None, td.labels, td.label_values, vec,
                                features_are_tf=True, coo=None,
                                texts=list(td.texts))
        coo = vec.fit_tf_coo(td.texts)
        return PreparedData(None, td.labels, td.label_values, vec,
                            features_are_tf=True, coo=coo)


@dataclasses.dataclass
class TextModel:
    inner: object
    vectorizer: TfIdfVectorizer
    label_values: np.ndarray

    def classify(self, text: str) -> tuple[str, float]:
        x = self.vectorizer.transform([text])
        if isinstance(self.inner, NaiveBayesModel):
            scores = self.inner.predict_log_joint(x)[0]
            z = scores - scores.max()
            probs = np.exp(z) / np.exp(z).sum()
        else:
            probs = self.inner.predict_proba(x)[0]
        c = int(np.argmax(probs))
        return str(self.label_values[c]), float(probs[c])


@dataclasses.dataclass(frozen=True)
class TextAlgorithmParams(Params):
    smoothing: float = 1.0  # NB
    reg: float = 0.0  # LR
    max_iters: int = 100  # LR


class TextNBAlgorithm(Algorithm):
    params_cls = TextAlgorithmParams
    params_aliases = {"lambda": "smoothing", "regParam": "reg"}

    def stage_model(self, pd: PreparedData):
        """One scatter-add pass over the COO term counts (or the dense
        matrix): transfer-bound where the host→device link is slow;
        --device=auto prices it."""
        from ..workflow.placement import StageModel

        if pd.coo is not None:
            doc_ptr, feat, cnt = pd.coo
            nbytes = feat.nbytes + cnt.nbytes + doc_ptr.nbytes
        elif pd.texts is not None:
            # deferred (streaming) featurize: the COO doesn't exist yet;
            # the corpus byte count is the right order-of-magnitude
            # proxy (~1 COO entry per ~6 chars of text)
            nbytes = sum(len(t) for t in pd.texts)
        else:
            nbytes = pd.features.nbytes
        return StageModel(bytes_to_device=nbytes, device_passes=1.0,
                          cpu_passes=1.0)

    def train(self, ctx, pd: PreparedData) -> TextModel:
        mesh = ctx.get_mesh() if ctx else None
        scale = pd.vectorizer.idf if pd.features_are_tf else None
        cfg = _pipeline_of(ctx)
        if pd.coo is None and pd.texts is not None:
            inner = self._train_streamed(pd, mesh, cfg)
        elif pd.coo is not None:
            doc_ptr, feat, cnt = pd.coo
            inner = train_naive_bayes_coo(
                doc_ptr, feat, cnt, pd.labels,
                n_classes=len(pd.label_values),
                n_features=pd.vectorizer.n_features,
                smoothing=self.params.smoothing,
                mesh=mesh, col_scale=scale, pipeline=cfg,
            )
        else:
            inner = train_naive_bayes(
                pd.features, pd.labels, len(pd.label_values),
                smoothing=self.params.smoothing,
                mesh=mesh, col_scale=scale, pipeline=cfg,
            )
        return TextModel(inner, pd.vectorizer, pd.label_values)

    def _train_streamed(self, pd: PreparedData, mesh, cfg) -> NaiveBayesModel:
        """Fully overlapped text path: tokenizer workers featurize doc
        chunk N+2 while chunk N+1 uploads and chunk N scatter-adds into
        the device stats. Produces the same model as the one-shot
        prepare+train (same integer additions; the idf column scale is
        finalized from the accumulated dfs after the last chunk)."""
        from ..workflow.input_pipeline import (
            PipelineConfig, chunk_ranges, prefetch,
        )
        from ..ops.linear import train_naive_bayes_coo_stream

        cfg = cfg or PipelineConfig.from_env()
        vec = pd.vectorizer
        texts, labels = pd.texts, pd.labels
        n_docs = len(texts)
        df_acc = np.zeros(vec.n_features, np.int64)

        def featurize(rng):
            s, e = rng
            doc_ptr, feat, cnt, df = vec.tf_coo_block(texts[s:e])
            cls = np.repeat(labels[s:e], np.diff(np.asarray(doc_ptr)))
            return cls, feat, cnt, df

        def blocks():
            # df accumulates on the CONSUMER side in arrival (=corpus)
            # order; int64 sums are exact so order is moot, but keeping
            # mutation out of the worker threads keeps them pure
            for cls, feat, cnt, df in prefetch(
                    chunk_ranges(n_docs, cfg.chunk_docs), featurize,
                    workers=cfg.workers, lookahead=cfg.depth + 1):
                np.add(df_acc, df, out=df_acc)
                yield cls, feat, cnt

        def idf_scale():
            return vec.set_idf_from_df(df_acc, n_docs)

        return train_naive_bayes_coo_stream(
            blocks(), labels, n_classes=len(pd.label_values),
            n_features=vec.n_features, smoothing=self.params.smoothing,
            mesh=mesh,
            col_scale=idf_scale if pd.features_are_tf else None,
            pipeline=cfg,
        )

    def predict(self, model: TextModel, query: dict) -> dict:
        category, confidence = model.classify(str(query["text"]))
        return {"category": category, "confidence": confidence}


class TextLRAlgorithm(TextNBAlgorithm):
    def stage_model(self, pd: PreparedData):
        """Inheriting NB's single-pass model would mis-price this as
        transfer-bound: text LR materializes the dense scaled [N, D]
        f32 matrix and runs max_iters L-BFGS passes over it — the same
        iterate-on-resident-data shape as classification LR, with the
        same measured 10x CPU compute-intensity factor."""
        from ..workflow.placement import StageModel

        n_bytes = len(pd.labels) * pd.vectorizer.n_features * 4
        iters = float(self.params.max_iters)
        return StageModel(bytes_to_device=n_bytes, device_passes=iters,
                          cpu_passes=iters * 10.0)

    def train(self, ctx, pd: PreparedData) -> TextModel:
        features = pd.dense_tf()
        if pd.features_are_tf:
            # LR is nonlinear in x — the idf scale can't fold into the
            # stats like NB's; one explicit scaled materialization
            features = features * pd.vectorizer.idf
        inner = train_logistic_regression(
            features, pd.labels, len(pd.label_values),
            reg=self.params.reg, max_iters=self.params.max_iters,
            mesh=ctx.get_mesh() if ctx else None,
            pipeline=_pipeline_of(ctx),
        )
        return TextModel(inner, pd.vectorizer, pd.label_values)


class TextClassificationEngine(EngineFactory):
    def apply(self) -> Engine:
        return Engine(
            data_source_class=TextDataSource,
            preparator_class=TextPreparator,
            algorithm_class_map={
                "nb": TextNBAlgorithm,
                "lr": TextLRAlgorithm,
                "": TextNBAlgorithm,
            },
        )
