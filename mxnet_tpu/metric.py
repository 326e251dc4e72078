"""Evaluation metrics — streaming (sum, count) accumulators.

Capability parity with reference ``python/mxnet/metric.py`` (the
EvalMetric hierarchy and ``create``/``np`` factories), re-designed
rather than transcribed: every metric is a vectorized per-batch scoring
hook (``_score(label, pred) -> (sum, count)``) behind ONE shared update
pipeline that does the device→host conversion once. No per-sample
python loops anywhere — F1 comes from whole-batch confusion counts,
top-k from a single argpartition, perplexity from take_along_axis.
"""
from __future__ import annotations

import math

import numpy

from .ndarray import NDArray


def check_label_shapes(labels, preds, shape=0):
    """Raise on label/pred arity (or shape, with shape=1) mismatch."""
    a = len(labels) if shape == 0 else labels.shape
    b = len(preds) if shape == 0 else preds.shape
    if a != b:
        raise ValueError(
            "Shape of labels {} does not match shape of predictions {}"
            .format(a, b))


def _host(x):
    """One conversion point: NDArray/jax array -> numpy."""
    return x.asnumpy() if isinstance(x, NDArray) else numpy.asarray(x)


class EvalMetric(object):
    """Base accumulator. Subclasses implement ``_score(label, pred)``
    returning a (metric_sum, instance_count) pair per output batch; the
    base class owns conversion, accumulation, and reporting. The
    ``num``-slot variant (one counter per output) is kept for heads that
    report per-output values (e.g. detection losses)."""

    def __init__(self, name, num=None):
        self.name = name
        self.num = num
        self.reset()

    # -- subclass hook --------------------------------------------------
    def _score(self, label, pred):
        raise NotImplementedError()

    # -- shared pipeline ------------------------------------------------
    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        if self.num is None:
            for label, pred in zip(labels, preds):
                s, n = self._score(_host(label), _host(pred))
                self.sum_metric += s
                self.num_inst += n
        else:
            for i, (label, pred) in enumerate(zip(labels, preds)):
                s, n = self._score(_host(label), _host(pred))
                self.sum_metric[i] += s
                self.num_inst[i] += n

    def reset(self):
        zero = (0.0, 0) if self.num is None else (
            [0.0] * self.num, [0] * self.num)
        self.sum_metric, self.num_inst = zero[0], zero[1]

    def _ratio(self, s, n):
        return s / n if n else float("nan")

    def get(self):
        if self.num is None:
            return (self.name, self._ratio(self.sum_metric, self.num_inst))
        return (
            ["%s_%d" % (self.name, i) for i in range(self.num)],
            [self._ratio(s, n)
             for s, n in zip(self.sum_metric, self.num_inst)],
        )

    def get_name_value(self):
        name, value = self.get()
        if not isinstance(name, list):
            name, value = [name], [value]
        return list(zip(name, value))

    def __str__(self):
        return "EvalMetric: {}".format(dict(self.get_name_value()))


def _as_class_ids(label, pred):
    """Hard class ids from (label, pred): argmax pred over the channel
    axis when it still carries probabilities. Probabilities are
    detected by SIZE, not exact shape: an (N,1)-vs-(N,) layout skew
    (DataIter column labels + id predictions) must not be mistaken for
    an (N,C) probability matrix — the old shape!=shape test sent (N,)
    id predictions into argmax(axis=1) and crashed. Size-matched FLOAT
    predictions that still look like probabilities (any value strictly
    inside (0, 1) — a single-column sigmoid head) are thresholded at
    0.5: the old straight int-cast truncated every such probability to
    class 0 (ADVICE r5)."""
    if pred.size == label.size:
        pred_ids = pred
        if pred_ids.dtype.kind == "f" and pred_ids.size:
            frac = (pred_ids > 0.0) & (pred_ids < 1.0)
            if frac.any():
                pred_ids = (pred_ids >= 0.5)
    else:
        pred_ids = pred.argmax(axis=1)
    return label.astype("int64").ravel(), pred_ids.astype("int64").ravel()


class Accuracy(EvalMetric):
    def __init__(self):
        super().__init__("accuracy")

    def _score(self, label, pred):
        lab, ids = _as_class_ids(label, pred)
        check_label_shapes(lab, ids, shape=1)
        return int((ids == lab).sum()), lab.size


class TopKAccuracy(EvalMetric):
    """Hit if the true class is among the k highest-scoring classes."""

    def __init__(self, **kwargs):
        self.top_k = kwargs.get("top_k", 1)
        assert self.top_k > 1, "Please use Accuracy if top_k is no more than 1"
        super().__init__("top_k_accuracy_%d" % self.top_k)

    def _score(self, label, pred):
        assert pred.ndim <= 2, "Predictions should be no more than 2 dims"
        lab = label.astype("int64").ravel()
        if pred.ndim == 1:
            return int((pred.astype("int64") == lab).sum()), lab.size
        k = min(self.top_k, pred.shape[1])
        # one partial sort per batch: top-k columns, order irrelevant
        topk = numpy.argpartition(pred, -k, axis=1)[:, -k:]
        hit = (topk == lab[:, None]).any(axis=1)
        return int(hit.sum()), lab.size


class F1(EvalMetric):
    """Binary F1 from whole-batch confusion counts; accumulated as one
    score per batch (matching the reference's averaging convention)."""

    def __init__(self):
        super().__init__("f1")

    def _score(self, label, pred):
        lab, ids = _as_class_ids(label, pred)
        if numpy.unique(lab).size > 2:
            raise ValueError(
                "F1 currently only supports binary classification.")
        tp = int(((ids == 1) & (lab == 1)).sum())
        fp = int(((ids == 1) & (lab == 0)).sum())
        fn = int(((ids == 0) & (lab == 1)).sum())
        precision = tp / (tp + fp) if tp + fp else 0.0
        recall = tp / (tp + fn) if tp + fn else 0.0
        f1 = (2 * precision * recall / (precision + recall)
              if precision + recall else 0.0)
        return f1, 1


class Perplexity(EvalMetric):
    """exp of the mean negative log-probability of the true tokens,
    with an optional ignored (padding) label id."""

    def __init__(self, ignore_label, axis=-1):
        super().__init__("Perplexity")
        self.ignore_label = ignore_label
        self.axis = axis

    def _score(self, label, pred):
        n_class = pred.shape[-1]
        assert label.size == pred.size // n_class, (
            "shape mismatch: %s vs. %s" % (label.shape, pred.shape))
        flat = pred.reshape(-1, n_class)
        ids = label.astype("int64").reshape(-1, 1)
        probs = numpy.take_along_axis(flat, ids, axis=1).ravel()
        count = ids.size
        if self.ignore_label is not None:
            keep = (ids.ravel() != self.ignore_label)
            probs = numpy.where(keep, probs, 1.0)
            count = int(keep.sum())
        nll = -numpy.log(numpy.maximum(probs, 1e-10)).sum()
        return float(nll), count

    def get(self):
        if not self.num_inst:
            return (self.name, float("nan"))
        return (self.name, math.exp(self.sum_metric / self.num_inst))


class _Regression(EvalMetric):
    """Shared shape handling for elementwise regression metrics: a 1-d
    label aligns against (N, 1) predictions (the reference's
    column-vector regression convention), one score per batch.

    A 1-d PREDICTION is columnized too: without that, (N,1) label minus
    (N,) pred broadcasts to an (N,N) all-pairs matrix and the metric
    silently reports ~2x the label variance regardless of fit — found
    via examples/matrix_factorization.py, whose scalar-dot predictions
    are 1-d (the reference shares the label reshape but its examples
    always emit (N,1) FC predictions, hiding the hazard)."""

    def _score(self, label, pred):
        if label.ndim == 1:
            label = label[:, None]
        if pred.ndim == 1:
            pred = pred[:, None]
        return float(self._agg(label, pred)), 1


class MAE(_Regression):
    def __init__(self):
        super().__init__("mae")

    @staticmethod
    def _agg(label, pred):
        return numpy.abs(label - pred).mean()


class MSE(_Regression):
    def __init__(self):
        super().__init__("mse")

    @staticmethod
    def _agg(label, pred):
        return numpy.square(label - pred).mean()


class RMSE(_Regression):
    def __init__(self):
        super().__init__("rmse")

    @staticmethod
    def _agg(label, pred):
        return math.sqrt(numpy.square(label - pred).mean())


class CrossEntropy(EvalMetric):
    def __init__(self, eps=1e-8):
        super().__init__("cross-entropy")
        self.eps = eps

    def _score(self, label, pred):
        lab = label.ravel().astype("int64")
        assert lab.shape[0] == pred.shape[0]
        probs = pred[numpy.arange(lab.size), lab]
        return float(-numpy.log(probs + self.eps).sum()), lab.size


class Loss(EvalMetric):
    """Mean of a loss the network computes itself (a ``MakeLoss`` head):
    the name later MXNet gave this metric. Labels are ignored. Only the
    outputs named by ``outputs`` (indices; default the first) are read,
    so a symbol may carry side outputs behind ``BlockGrad`` after its
    loss, and the fetch is the size of the loss, not of a probability
    table."""

    def __init__(self, name="loss", outputs=(0,)):
        super().__init__(name)
        self._outputs = tuple(outputs)

    def update(self, _, preds):
        for i in self._outputs:
            loss = _host(preds[i])
            self.sum_metric += float(loss.sum())
            self.num_inst += loss.size


class CustomMetric(EvalMetric):
    """Adapter for a user eval fn of (label_np, pred_np); the fn may
    return a bare score (counted per batch) or a (sum, count) pair."""

    def __init__(self, feval, name=None, allow_extra_outputs=False):
        if name is None:
            name = feval.__name__
            if "<" in name:
                name = "custom(%s)" % name
        super().__init__(name)
        self._feval = feval
        self._allow_extra_outputs = allow_extra_outputs

    def update(self, labels, preds):
        if not self._allow_extra_outputs:
            check_label_shapes(labels, preds)
        EvalMetric.update(
            self, list(labels)[:len(preds)], list(preds)[:len(labels)])

    def _score(self, label, pred):
        out = self._feval(label, pred)
        return out if isinstance(out, tuple) else (out, 1)


class CompositeEvalMetric(EvalMetric):
    """Fan-out wrapper over child metrics."""

    def __init__(self, **kwargs):
        super().__init__("composite")
        self.metrics = list(kwargs.get("metrics", []))

    def add(self, metric):
        self.metrics.append(metric)

    def get_metric(self, index):
        try:
            return self.metrics[index]
        except IndexError:
            raise ValueError(
                "Metric index {} is out of range".format(index))

    def update(self, labels, preds):
        for m in self.metrics:
            m.update(labels, preds)

    def reset(self):
        for m in getattr(self, "metrics", []):
            m.reset()

    def get(self):
        pairs = [m.get() for m in self.metrics]
        return ([n for n, _ in pairs], [v for _, v in pairs])


def np(numpy_feval, name=None, allow_extra_outputs=False):
    """Wrap a bare numpy eval function as a metric."""
    metric = CustomMetric(numpy_feval, name, allow_extra_outputs)
    return metric


_REGISTRY = {
    "acc": Accuracy,
    "accuracy": Accuracy,
    "ce": CrossEntropy,
    "f1": F1,
    "loss": Loss,
    "mae": MAE,
    "mse": MSE,
    "rmse": RMSE,
    "top_k_accuracy": TopKAccuracy,
}


def create(metric, **kwargs):
    """str name / callable / EvalMetric / list -> EvalMetric.

    Anything already speaking the metric protocol (update/reset/get —
    e.g. example-level duck-typed metrics like SSD's MultiBoxMetric)
    passes through unchanged."""
    if isinstance(metric, EvalMetric):
        return metric
    if all(hasattr(metric, m) for m in ("update", "reset", "get")):
        return metric
    if callable(metric):
        return CustomMetric(metric)
    if isinstance(metric, list):
        out = CompositeEvalMetric()
        for m in metric:
            out.add(create(m, **kwargs))
        return out
    try:
        cls = _REGISTRY[metric.lower()]
    except KeyError:
        raise ValueError("Metric must be either callable or in {}".format(
            sorted(_REGISTRY)))
    return cls(**kwargs)
