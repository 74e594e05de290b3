"""Evaluation metrics.

API parity with the reference metric registry (python/mxnet/metric.py)
but a different internal design: every concrete metric is a pure
per-batch *measure* — ``_measure(label, pred) -> (contribution, weight)``
over numpy arrays — and the ``EvalMetric`` base owns coercion from
device arrays, pairing of output/label lists, and running accumulation.
Host transfer happens exactly once per batch at the measure boundary
(metrics are scalar bookkeeping; keeping them out of the jitted step is
deliberate — see module/fused_step.py for the on-device loss path).
"""
from __future__ import annotations

import numpy as np

from .ndarray import NDArray
from .observability import instrument as _instrument

__all__ = [
    "EvalMetric", "CompositeEvalMetric", "Accuracy", "TopKAccuracy", "F1",
    "Perplexity", "MAE", "MSE", "RMSE", "CrossEntropy",
    "NegativeLogLikelihood", "PearsonCorrelation", "Loss", "Torch", "Caffe",
    "CustomMetric", "create", "register", "np_metric", "check_label_shapes",
]


def _host(array):
    """Bring one label/pred onto the host as a numpy array.  In the fit
    loop this is where the host waits for the step: the ``metric:fetch``
    phase ends as the outputs land, which is where the loop's tracker
    first sees the device run dry (observability/instrument.py)."""
    if isinstance(array, NDArray):
        with _instrument.phase("metric:fetch"):
            return array.asnumpy()
    return np.asarray(array)


def check_label_shapes(labels, preds, shape=0):
    """Validate that labels and preds pair up (count, or full shape)."""
    a = labels.shape if shape else len(labels)
    b = preds.shape if shape else len(preds)
    if a != b:
        raise ValueError(
            "Shape of labels {} does not match shape of predictions {}"
            .format(a, b))


class EvalMetric:
    """Running (weighted) average of a per-batch measure.

    Subclasses implement ``_measure(label, pred)`` on numpy arrays and
    return ``(contribution, weight)``; the base accumulates
    ``sum_metric += contribution`` and ``num_inst += weight`` and reports
    their ratio from :meth:`get`.
    """

    def __init__(self, name, output_names=None, label_names=None, **kwargs):
        self.name = str(name)
        self.output_names = output_names
        self.label_names = label_names
        self._init_kwargs = kwargs
        self.reset()

    # -- accumulation protocol -------------------------------------------
    def _measure(self, label, pred):
        raise NotImplementedError(
            "%s must implement _measure or override update"
            % type(self).__name__)

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            contribution, weight = self._measure(_host(label), _host(pred))
            self.sum_metric += contribution
            self.num_inst += weight

    def update_dict(self, label, pred):
        """Update from {name: array} dicts (Module's named outputs)."""
        preds = ([pred[k] for k in self.output_names]
                 if self.output_names is not None else list(pred.values()))
        labels = ([label[k] for k in self.label_names]
                  if self.label_names is not None else list(label.values()))
        self.update(labels, preds)

    def reset(self):
        self.sum_metric = 0.0
        self.num_inst = 0

    # -- reporting -------------------------------------------------------
    def get(self):
        value = (self.sum_metric / self.num_inst if self.num_inst
                 else float("nan"))
        return (self.name, value)

    def get_name_value(self):
        names, values = self.get()
        if not isinstance(names, list):
            names, values = [names], [values]
        return list(zip(names, values))

    def get_config(self):
        config = dict(self._init_kwargs)
        config.update(metric=type(self).__name__, name=self.name,
                      output_names=self.output_names,
                      label_names=self.label_names)
        return config

    def __str__(self):
        return "EvalMetric: {}".format(dict(self.get_name_value()))


# ---------------------------------------------------------------------------
# registry

_REGISTRY = {}


def register(*aliases):
    """Class decorator registering a metric under its name plus aliases.

    Usable bare (``@register``) or with explicit alias strings
    (``@register("acc")``).
    """
    def _add(cls, extra=()):
        for key in (cls.__name__.lower(), *extra):
            _REGISTRY[key] = cls
        return cls

    if len(aliases) == 1 and isinstance(aliases[0], type):
        return _add(aliases[0])
    return lambda cls: _add(cls, aliases)


def create(metric, *args, **kwargs):
    """Build a metric from a name, callable, instance, or list thereof."""
    if isinstance(metric, EvalMetric):
        return metric
    if callable(metric):
        return CustomMetric(metric, *args, **kwargs)
    if isinstance(metric, (list, tuple)):
        out = CompositeEvalMetric()
        for m in metric:
            out.add(create(m, *args, **kwargs))
        return out
    if isinstance(metric, str):
        cls = _REGISTRY.get(metric.lower())
        if cls is None:
            raise ValueError(
                "Metric must be either callable or str; unknown %s" % metric)
        return cls(*args, **kwargs)
    raise TypeError("invalid metric type %s" % type(metric))


# ---------------------------------------------------------------------------
# composite

@register("composite")
class CompositeEvalMetric(EvalMetric):
    """Fan updates out to a list of child metrics; report all of them."""

    def __init__(self, metrics=None, name="composite",
                 output_names=None, label_names=None):
        super().__init__(name, output_names=output_names,
                         label_names=label_names)
        self.metrics = [create(m) for m in (metrics or [])]

    def add(self, metric):
        self.metrics.append(create(metric))

    def get_metric(self, index):
        return self.metrics[index]

    def update(self, labels, preds):
        for m in self.metrics:
            m.update(labels, preds)

    def update_dict(self, labels, preds):
        for m in self.metrics:
            m.update_dict(labels, preds)

    def reset(self):
        for m in getattr(self, "metrics", ()):
            m.reset()

    def get(self):
        names, values = [], []
        for m in self.metrics:
            name, value = m.get()
            names.extend([name] if isinstance(name, str) else name)
            values.extend([value] if np.isscalar(value) else value)
        return (names, values)


# ---------------------------------------------------------------------------
# classification

@register("acc")
class Accuracy(EvalMetric):
    """Fraction of predictions equal to the label.

    Accepts either class scores (argmax'd over ``axis``) or already-decoded
    class indices.
    """

    def __init__(self, axis=1, name="accuracy",
                 output_names=None, label_names=None):
        super().__init__(name, axis=axis, output_names=output_names,
                         label_names=label_names)
        self.axis = axis

    def _measure(self, label, pred):
        if pred.ndim > 1 and pred.shape != label.shape:
            pred = pred.argmax(axis=self.axis)
        label = label.astype(np.int64).ravel()
        pred = pred.astype(np.int64).ravel()
        check_label_shapes(label, pred, shape=1)
        return float((pred == label).sum()), label.size


@register("top_k_accuracy", "top_k_acc")
class TopKAccuracy(EvalMetric):
    """Fraction of samples whose label lands in the top-k scores."""

    def __init__(self, top_k=1, name="top_k_accuracy",
                 output_names=None, label_names=None):
        if top_k <= 1:
            raise AssertionError(
                "Please use Accuracy if top_k is no more than 1")
        super().__init__("%s_%d" % (name, top_k), top_k=top_k,
                         output_names=output_names, label_names=label_names)
        self.top_k = top_k

    def _measure(self, label, pred):
        if pred.ndim > 2:
            raise AssertionError("Predictions should be no more than 2 dims")
        label = label.astype(np.int64).ravel()
        if pred.ndim == 1:
            hits = (pred.astype(np.int64) == label).sum()
        else:
            k = min(self.top_k, pred.shape[1])
            # one partial sort per batch; membership test is vectorized
            top = np.argpartition(pred.astype(np.float32), -k, axis=1)[:, -k:]
            hits = (top == label[:, None]).any(axis=1).sum()
        return float(hits), label.size


@register
class F1(EvalMetric):
    """Mean per-batch F1 for binary {0,1} labels."""

    def __init__(self, name="f1", output_names=None, label_names=None):
        super().__init__(name, output_names=output_names,
                         label_names=label_names)

    def _measure(self, label, pred):
        label = label.astype(np.int64).ravel()
        decided = pred.argmax(axis=1).ravel()
        check_label_shapes(label, decided, shape=1)
        if np.unique(label).size > 2:
            raise ValueError(
                "F1 currently only supports binary classification.")
        tp = float(np.sum((decided == 1) & (label == 1)))
        fp = float(np.sum((decided == 1) & (label == 0)))
        fn = float(np.sum((decided == 0) & (label == 1)))
        precision = tp / (tp + fp) if tp + fp else 0.0
        recall = tp / (tp + fn) if tp + fn else 0.0
        denom = precision + recall
        return (2.0 * precision * recall / denom if denom else 0.0), 1


# ---------------------------------------------------------------------------
# likelihood family

class _PickedLogProb(EvalMetric):
    """Shared machinery: gather prob of the true class per sample."""

    def __init__(self, eps, name, output_names, label_names):
        super().__init__(name, eps=eps, output_names=output_names,
                         label_names=label_names)
        self.eps = eps

    def _picked(self, label, pred):
        label = label.astype(np.int64).ravel()
        assert label.shape[0] == pred.shape[0], (label.shape, pred.shape)
        return pred[np.arange(label.shape[0]), label]

    def _measure(self, label, pred):
        prob = self._picked(label, pred)
        return float(-np.log(prob + self.eps).sum()), prob.shape[0]


@register("ce", "crossentropy")
class CrossEntropy(_PickedLogProb):
    def __init__(self, eps=1e-12, name="cross-entropy",
                 output_names=None, label_names=None):
        super().__init__(eps, name, output_names, label_names)


@register("nll_loss", "negativeloglikelihood")
class NegativeLogLikelihood(_PickedLogProb):
    def __init__(self, eps=1e-12, name="nll-loss",
                 output_names=None, label_names=None):
        super().__init__(eps, name, output_names, label_names)


@register
class Perplexity(EvalMetric):
    """exp(mean negative log prob), optionally masking one ignore label.

    Accumulates ``perplexity * tokens`` so composing batches of unequal
    size stays a token-weighted mean, matching the reference semantics.
    """

    def __init__(self, ignore_label, axis=-1, name="perplexity",
                 output_names=None, label_names=None):
        super().__init__(name, ignore_label=ignore_label, axis=axis,
                         output_names=output_names, label_names=label_names)
        self.ignore_label = ignore_label
        self.axis = axis

    def _pair_nll(self, label, pred):
        """(total nll, token count) for one output/label pair."""
        classes = pred.shape[-1]
        assert label.size * classes == pred.size, \
            "shape mismatch: %s vs. %s" % (label.shape, pred.shape)
        flat = label.astype(np.int64).ravel()
        prob = pred.reshape(-1, classes)[np.arange(flat.size), flat]
        tokens = flat.size
        if self.ignore_label is not None:
            keep = flat != self.ignore_label
            prob = np.where(keep, prob, 1.0)
            tokens = int(keep.sum())
        return float(-np.log(np.maximum(prob, 1e-10)).sum()), tokens

    def update(self, labels, preds):
        # pool nll/tokens across every output pair BEFORE exponentiating:
        # exp is nonlinear, so per-pair perplexities cannot be averaged
        assert len(labels) == len(preds)
        nll, tokens = 0.0, 0
        for label, pred in zip(labels, preds):
            pair_nll, pair_tokens = self._pair_nll(_host(label), _host(pred))
            nll += pair_nll
            tokens += pair_tokens
        if tokens > 0:
            self.sum_metric += float(np.exp(nll / tokens)) * tokens
            self.num_inst += tokens


# ---------------------------------------------------------------------------
# regression

class _Regression(EvalMetric):
    """Shared 2-D coercion for elementwise regression measures."""

    @staticmethod
    def _as_2d(a):
        return a.reshape(a.shape[0], -1) if a.ndim > 1 else a[:, None]

    def _measure(self, label, pred):
        return self._residual(self._as_2d(label), self._as_2d(pred)), 1


@register
class MAE(_Regression):
    def __init__(self, name="mae", output_names=None, label_names=None):
        super().__init__(name, output_names=output_names,
                         label_names=label_names)

    def _residual(self, label, pred):
        return float(np.abs(label - pred).mean())


@register
class MSE(_Regression):
    def __init__(self, name="mse", output_names=None, label_names=None):
        super().__init__(name, output_names=output_names,
                         label_names=label_names)

    def _residual(self, label, pred):
        return float(np.square(label - pred).mean())


@register
class RMSE(_Regression):
    def __init__(self, name="rmse", output_names=None, label_names=None):
        super().__init__(name, output_names=output_names,
                         label_names=label_names)

    def _residual(self, label, pred):
        return float(np.sqrt(np.square(label - pred).mean()))


@register("pearsonr")
class PearsonCorrelation(EvalMetric):
    def __init__(self, name="pearsonr", output_names=None, label_names=None):
        super().__init__(name, output_names=output_names,
                         label_names=label_names)

    def _measure(self, label, pred):
        check_label_shapes(label, pred, shape=1)
        return float(np.corrcoef(pred.ravel(), label.ravel())[0, 1]), 1


# ---------------------------------------------------------------------------
# loss passthrough + custom

@register
class Loss(EvalMetric):
    """Mean of raw output values (for networks that emit a loss head)."""

    def __init__(self, name="loss", output_names=None, label_names=None):
        super().__init__(name, output_names=output_names,
                         label_names=label_names)

    def update(self, _labels, preds):
        for pred in preds:
            host = _host(pred)
            self.sum_metric += float(host.sum())
            self.num_inst += host.size


@register
class Torch(Loss):
    """Alias kept for checkpoint/config compatibility."""

    def __init__(self, name="torch", output_names=None, label_names=None):
        super().__init__(name, output_names, label_names)


@register
class Caffe(Loss):
    """Alias kept for checkpoint/config compatibility."""

    def __init__(self, name="caffe", output_names=None, label_names=None):
        super().__init__(name, output_names, label_names)


@register
class CustomMetric(EvalMetric):
    """Wrap a ``feval(label_np, pred_np)`` callable as a metric.

    ``feval`` may return a bare value (weight 1) or ``(sum, count)``.
    """

    def __init__(self, feval, name=None, allow_extra_outputs=False,
                 output_names=None, label_names=None):
        if name is None:
            name = feval.__name__
            if "<" in name:  # lambdas render as '<lambda>'
                name = "custom(%s)" % name
        super().__init__(name, feval=feval,
                         allow_extra_outputs=allow_extra_outputs,
                         output_names=output_names, label_names=label_names)
        self._feval = feval
        self._allow_extra_outputs = allow_extra_outputs

    def update(self, labels, preds):
        if not self._allow_extra_outputs:
            check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            result = self._feval(_host(label), _host(pred))
            if isinstance(result, tuple):
                contribution, weight = result
            else:
                contribution, weight = result, 1
            self.sum_metric += contribution
            self.num_inst += weight


def np_metric(name=None, allow_extra_outputs=False):
    """Decorator turning a numpy feval into a CustomMetric instance."""
    def _wrap(numpy_feval):
        feval_name = name or numpy_feval.__name__
        numpy_feval.__name__ = feval_name
        return CustomMetric(numpy_feval, feval_name, allow_extra_outputs)
    return _wrap


np_ = np_metric
