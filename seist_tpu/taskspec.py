"""Task specifications: io-item catalog + per-model-family task configs.

This is the TPU-native replacement for the reference's ``config.py`` (see
/root/reference/config.py:20-435). The reference keys its model configs by
regex and stores loss constructors via ``functools.partial``; here the same
information is typed data:

* :class:`IOItem` — one entry of the io-item catalog
  (/root/reference/config.py:207-264).
* :class:`TaskSpec` — loss factory, input/label/eval lists and optional
  transforms for one model family (/root/reference/config.py:64-186).

Data layout convention: this framework is **channels-last** — waveforms are
``(N, L, C)`` and dense outputs are ``(N, L, C)`` — the layout XLA prefers on
TPU. The reference is channels-first ``(N, C, L)``; transposition happens only
in parity tooling (tools/torch2flax.py).
"""

from __future__ import annotations

import math
import re
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax.numpy as jnp

# ---------------------------------------------------------------------------
# io-item catalog
# ---------------------------------------------------------------------------

SOFT = "soft"
VALUE = "value"
ONEHOT = "onehot"
TOKENS = "tokens"  # integer ids of a sequence, (L,) int32, never normalised
_IO_KINDS = (SOFT, VALUE, ONEHOT, TOKENS)

AVAILABLE_METRICS = (
    "precision",
    "recall",
    "f1",
    "mean",
    "rmse",
    "mae",
    "mape",
    "r2",
)


@dataclass(frozen=True)
class IOItem:
    """One io-item (model input or label). Ref: config.py:207-264."""

    name: str
    kind: str
    metrics: Tuple[str, ...] = ()
    num_classes: Optional[int] = None

    def __post_init__(self):
        if self.kind not in _IO_KINDS:
            raise ValueError(f"Unknown io-item kind '{self.kind}' for '{self.name}'")
        unknown = set(self.metrics) - set(AVAILABLE_METRICS)
        if unknown:
            raise ValueError(f"Unknown metrics {unknown} for io-item '{self.name}'")
        if self.kind == ONEHOT and not self.num_classes:
            raise ValueError(f"onehot io-item '{self.name}' needs num_classes")


_WAVE_METRICS = ("mean", "rmse", "mae")
_PICK_METRICS = ("precision", "recall", "f1", "mean", "rmse", "mae", "mape")
_VALUE_METRICS = ("mean", "rmse", "mae", "mape", "r2")
_REGR_METRICS = ("mean", "rmse", "mae", "r2")
_CLS_METRICS = ("precision", "recall", "f1")

IO_ITEMS: Dict[str, IOItem] = {
    item.name: item
    for item in [
        IOItem("z", SOFT, _WAVE_METRICS),
        IOItem("n", SOFT, _WAVE_METRICS),
        IOItem("e", SOFT, _WAVE_METRICS),
        IOItem("dz", SOFT, _WAVE_METRICS),
        IOItem("dn", SOFT, _WAVE_METRICS),
        IOItem("de", SOFT, _WAVE_METRICS),
        IOItem("non", SOFT, ()),
        IOItem("det", SOFT, _CLS_METRICS),
        IOItem("ppk", SOFT, _PICK_METRICS),
        IOItem("spk", SOFT, _PICK_METRICS),
        IOItem("ppk+", SOFT, ()),
        IOItem("spk+", SOFT, ()),
        IOItem("det+", SOFT, ()),
        IOItem("ppks", VALUE, _VALUE_METRICS),
        IOItem("spks", VALUE, _VALUE_METRICS),
        IOItem("emg", VALUE, _REGR_METRICS),
        IOItem("smg", VALUE, _REGR_METRICS),
        IOItem("baz", VALUE, _REGR_METRICS),
        IOItem("dis", VALUE, _REGR_METRICS),
        IOItem("pmp", ONEHOT, _CLS_METRICS, num_classes=2),
        IOItem("clr", ONEHOT, _CLS_METRICS, num_classes=2),
        # A token task: the input is a window of ids, the label the same
        # window shifted by one (-1 where the window ends: no target).
        IOItem("ids", TOKENS, ()),
        IOItem("next_ids", TOKENS, ()),
    ]
}


def get_io_items(kind: Optional[str] = None) -> List[str]:
    if kind is None:
        return list(IO_ITEMS)
    return [k for k, v in IO_ITEMS.items() if v.kind == kind]


def get_kind(name: str) -> str:
    return IO_ITEMS[name].kind


def get_num_classes(name: str) -> int:
    item = IO_ITEMS[name]
    if item.kind != ONEHOT:
        raise ValueError(f"io-item '{name}' is '{item.kind}', not onehot")
    return int(item.num_classes)


def get_metrics(name: str) -> List[str]:
    if name not in IO_ITEMS:
        raise KeyError(f"Unknown io-item '{name}', supported: {list(IO_ITEMS)}")
    return list(IO_ITEMS[name].metrics)


# ---------------------------------------------------------------------------
# Task specs
# ---------------------------------------------------------------------------

IOName = Union[str, Tuple[str, ...]]


def _deg2rad(x):
    return x * (math.pi / 180.0)


def _baz_targets_to_cos_sin(x):
    """baz scalar degrees -> (cos, sin) pair. Ref: config.py:102-105."""
    r = _deg2rad(x)
    return (jnp.cos(r), jnp.sin(r))


def _baz_outputs_to_deg(x):
    """(cos, sin) pair -> degrees via atan2. Ref: config.py:107-109."""
    return jnp.arctan2(x[1], x[0]) * (180.0 / math.pi)


def _magnet_results(x):
    """Keep only the mean prediction (drop log-variance). Ref: config.py:94."""
    return x[:, 0].reshape(-1, 1)


def _softmax_each(xs):
    """Softmax every element of a tuple of outputs. Ref: config.py:134."""
    return [jnp.asarray(jnp.exp(x) / jnp.sum(jnp.exp(x), axis=-1, keepdims=True)) for x in xs]


@dataclass(frozen=True)
class TaskSpec:
    """Task configuration for one model family. Ref: config.py:64-186.

    ``loss`` is a zero-arg factory returning a loss callable
    ``loss(preds, targets) -> scalar`` (see seist_tpu/models/losses.py).
    """

    pattern: str
    loss: Callable[[], Any]
    inputs: Tuple[IOName, ...]
    labels: Tuple[IOName, ...]
    eval: Tuple[str, ...]
    targets_transform_for_loss: Optional[Callable] = None
    outputs_transform_for_loss: Optional[Callable] = None
    outputs_transform_for_results: Optional[Callable] = None
    #: A token task: integer ids in, logits over a vocabulary out, scored by
    #: loss and token accuracy (no picker, no per-task metrics). Its train
    #: step hands back the model's ``aux`` counts in place of the outputs
    #: (the logits of one step are a gigabyte nobody reads).
    tokens: bool = False

    def matches(self, model_name: str) -> bool:
        return bool(re.findall(self.pattern, model_name))


def _build_task_specs() -> List[TaskSpec]:
    # Imported lazily to avoid a models <-> taskspec import cycle.
    from seist_tpu.models import losses as L

    ws = lambda w: tuple(w)  # noqa: E731  (readability for loss weights)

    return [
        # ------------------------------------------------ PhaseNet (config.py:67-75)
        TaskSpec(
            pattern="phasenet",
            loss=lambda: L.CELoss(weight=[1.0, 1.0, 1.0]),
            inputs=(("z", "n", "e"),),
            labels=(("non", "ppk", "spk"),),
            eval=("ppk", "spk"),
        ),
        # ------------------------------------------- EQTransformer (config.py:77-85)
        TaskSpec(
            pattern="eqtransformer",
            loss=lambda: L.BCELoss(weight=[0.5, 1.0, 1.0]),
            inputs=(("z", "n", "e"),),
            labels=(("det", "ppk", "spk"),),
            eval=("det", "ppk", "spk"),
        ),
        # -------------------------------------------------- MagNet (config.py:87-95)
        TaskSpec(
            pattern="magnet",
            loss=L.MousaviLoss,
            inputs=(("z", "n", "e"),),
            labels=("emg",),
            eval=("emg",),
            outputs_transform_for_results=_magnet_results,
        ),
        # --------------------------------------------- BAZ Network (config.py:97-110)
        TaskSpec(
            pattern="baz_network",
            loss=lambda: L.CombinationLoss(losses=[L.MSELoss, L.MSELoss]),
            inputs=(("z", "n", "e"),),
            labels=("baz",),
            eval=("baz",),
            targets_transform_for_loss=_baz_targets_to_cos_sin,
            outputs_transform_for_results=_baz_outputs_to_deg,
        ),
        # ------------------------------------------- DiTingMotion (config.py:127-135)
        TaskSpec(
            pattern="ditingmotion",
            loss=lambda: L.CombinationLoss(losses=[L.FocalLoss, L.FocalLoss]),
            inputs=(("z", "dz"),),
            labels=("clr", "pmp"),
            eval=("pmp",),
            outputs_transform_for_results=_softmax_each,
        ),
        # ------------------------------------------- SeisT dpk (config.py:137-145)
        TaskSpec(
            pattern="seist_.*?_dpk.*",
            loss=lambda: L.BCELoss(weight=[0.5, 1.0, 1.0]),
            inputs=(("z", "n", "e"),),
            labels=(("det", "ppk", "spk"),),
            eval=("det", "ppk", "spk"),
        ),
        # ------------------------------------------- SeisT pmp (config.py:147-155)
        TaskSpec(
            pattern="seist_.*?_pmp",
            loss=lambda: L.CELoss(weight=[1.0, 1.0]),
            inputs=(("z", "n", "e"),),
            labels=("pmp",),
            eval=("pmp",),
        ),
        # ------------------------------------------- SeisT emg (config.py:157-165)
        TaskSpec(
            pattern="seist_.*?_emg",
            loss=L.HuberLoss,
            inputs=(("z", "n", "e"),),
            labels=("emg",),
            eval=("emg",),
        ),
        # ------------------------------------------- SeisT baz (config.py:167-175)
        TaskSpec(
            pattern="seist_.*?_baz",
            loss=L.HuberLoss,
            inputs=(("z", "n", "e"),),
            labels=("baz",),
            eval=("baz",),
        ),
        # ------------------------------------------- SeisT dis (config.py:177-185)
        TaskSpec(
            pattern="seist_.*?_dis",
            loss=L.HuberLoss,
            inputs=(("z", "n", "e"),),
            labels=("dis",),
            eval=("dis",),
        ),
        # ----------------------------------- NemotronH (models/nemotron_h.py)
        TaskSpec(
            pattern="nemotron",
            loss=L.TokenCELoss,
            inputs=("ids",),
            labels=("next_ids",),
            eval=(),
            tokens=True,
        ),
    ]


_TASK_SPECS: Optional[List[TaskSpec]] = None


def task_specs() -> List[TaskSpec]:
    global _TASK_SPECS
    if _TASK_SPECS is None:
        _TASK_SPECS = _build_task_specs()
    return _TASK_SPECS


def get_task_spec(model_name: str) -> TaskSpec:
    """Resolve the unique TaskSpec for a model name. Ref: config.py:352-376."""
    from seist_tpu.registry import MODELS

    if len(MODELS) and model_name not in MODELS:
        raise KeyError(
            f"Unknown model: '{model_name}', registered: {MODELS.names()}"
        )
    hits = [s for s in task_specs() if s.matches(model_name)]
    if not hits:
        raise KeyError(f"Missing task spec for model '{model_name}'")
    if len(hits) > 1:
        raise KeyError(
            f"Model '{model_name}' matches multiple task specs: "
            f"{[s.pattern for s in hits]}"
        )
    return hits[0]


def flatten_io_names(names: Sequence[IOName]) -> List[str]:
    """Expand grouped io-names into a flat list. Ref: config.py:292-294."""
    out: List[str] = []
    for n in names:
        if isinstance(n, (tuple, list)):
            out.extend(n)
        else:
            out.append(n)
    return out


def get_num_inchannels(model_name: str) -> int:
    """Number of waveform input channels. Ref: config.py:396-408."""
    spec = get_task_spec(model_name)
    if spec.tokens:
        return 1  # one integer channel: the ids
    for inp in spec.inputs:
        if isinstance(inp, (tuple, list)) and IO_ITEMS[inp[0]].kind == SOFT:
            return len(inp)
    raise ValueError(f"Incorrect input channels for model '{model_name}': {spec.inputs}")


def make_loss(model_name: str):
    """Instantiate the loss for a model. Ref: config.py:421-432."""
    return get_task_spec(model_name).loss()


def validate(strict_models: bool = True) -> None:
    """Cross-check specs against the io-item catalog and the model registry.

    Mirrors the reference's import-time ``Config.check_and_init``
    (config.py:267-325). Called from ``seist_tpu.__init__`` after model
    registration so a bad spec fails fast.
    """
    from seist_tpu.registry import MODELS

    for spec in task_specs():
        for group_name, group in (("labels", spec.labels), ("inputs", spec.inputs)):
            unknown = set(flatten_io_names(group)) - set(IO_ITEMS)
            if unknown:
                raise NotImplementedError(
                    f"Task '{spec.pattern}': unknown {group_name}: {unknown}"
                )
        unknown_tasks = set(spec.eval) - set(IO_ITEMS)
        if unknown_tasks:
            raise NotImplementedError(
                f"Task '{spec.pattern}': unknown eval tasks: {unknown_tasks}"
            )

    if strict_models and len(MODELS):
        unused = [
            s.pattern
            for s in task_specs()
            if not any(s.matches(m) for m in MODELS.names())
        ]
        if unused:
            # Parity with the reference, which only warns (config.py:284-285).
            print(f"Useless task specs: {unused}")
