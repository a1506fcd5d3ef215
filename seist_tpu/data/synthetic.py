"""Synthetic seismogram dataset — no disk, fully deterministic.

Not in the reference (which has no test data strategy at all, SURVEY.md §4);
this dataset generates plausible 3-channel event waveforms (noise + damped
P/S wavelets) with every label the io-item catalog knows (ppks/spks, emg,
smg, pmp, clr, baz, dis, snr), so any registered model can run end-to-end —
tests, smoke runs, and bench.py all use it. Event ``idx`` is generated from
``default_rng(seed * 1e6 + idx)``: stable across epochs and worker layouts.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np
import pandas as pd

from seist_tpu.data.base import DatasetBase, Event
from seist_tpu.registry import register_dataset


def make_wavelet(
    rng: np.random.Generator, length: int, freq: float, fs: int
) -> np.ndarray:
    """Damped sinusoid: t*exp(-3t) envelope, random-phase carrier. Shared
    by this dataset and tools/fixtures.py (the parity fixture uses the same
    recipe)."""
    t = np.arange(length) / fs
    envelope = t * np.exp(-3.0 * t)
    carrier = np.sin(2 * np.pi * freq * t + rng.uniform(0, 2 * np.pi))
    return (envelope * carrier / (np.abs(envelope).max() + 1e-9)).astype(
        np.float32
    )


class Synthetic(DatasetBase):
    _name = "synthetic"
    _part_range = None
    _channels = ["z", "n", "e"]
    _sampling_rate = 50

    def __init__(
        self,
        *,
        num_events: int = 256,
        trace_samples: int = 12000,
        data_dir: str = "",
        cache: bool = True,
        **kwargs,
    ):
        self._num_events = num_events
        self._trace_samples = trace_samples
        # Wavelet synthesis costs ~2x what the downstream pipeline does
        # (profiled); caching makes repeated epochs measure the *pipeline*
        # (the role a real dataset's disk read plays is much cheaper).
        # Copies are returned because the preprocessor mutates in place.
        self._cache: dict = {} if cache else None
        super().__init__(data_dir=data_dir, **kwargs)

    def _load_meta_data(self) -> pd.DataFrame:
        meta = pd.DataFrame({"idx": np.arange(self._num_events)})
        return self._shuffle_and_split(meta)

    def _make_wavelet(self, rng, length: int, freq: float) -> np.ndarray:
        return make_wavelet(rng, length, freq, self._sampling_rate)

    @staticmethod
    def _copy_event(event: Event) -> Event:
        """Deep-enough copy: the preprocessor mutates data/label fields in
        place, so cached events must never be handed out aliased."""
        return {
            k: (v.copy() if isinstance(v, np.ndarray) else list(v))
            if isinstance(v, (np.ndarray, list))
            else v
            for k, v in event.items()
        }

    def _load_event_data(self, idx: int) -> Tuple[Event, dict]:
        if self._cache is not None and idx in self._cache:
            event, meta = self._cache[idx]
            return self._copy_event(event), dict(meta)
        row = self._meta_data.iloc[idx]
        rng = np.random.default_rng(int(self._seed) * 1_000_000 + int(row["idx"]))
        length = self._trace_samples
        n_ch = len(self._channels)

        data = rng.normal(0, 1.0, size=(n_ch, length)).astype(np.float32)
        ppk = int(rng.integers(length // 10, length // 2))
        spk = int(ppk + rng.integers(length // 20, length // 4))
        amp = rng.uniform(5.0, 20.0)
        wl = min(length - spk, length // 4)
        for c in range(n_ch):
            p_w = self._make_wavelet(rng, wl, freq=rng.uniform(4, 8))
            s_w = self._make_wavelet(rng, wl, freq=rng.uniform(1.5, 4))
            data[c, ppk : ppk + wl] += amp * p_w
            data[c, spk : spk + wl] += 1.6 * amp * s_w

        emg = float(np.clip(rng.normal(3.5, 1.0), 0, 8))
        event: Event = {
            "data": data,
            "ppks": [ppk],
            "spks": [spk],
            "emg": [emg],
            "smg": [float(np.clip(emg + rng.normal(0, 0.2), 0, 8))],
            "pmp": [int(rng.integers(0, 2))],
            "clr": [int(rng.integers(0, 2))],
            "baz": [float(rng.uniform(0, 360))],
            "dis": [float(rng.uniform(0, 330))],
            "snr": np.full(n_ch, 20.0, dtype=np.float32),
        }
        meta = {"idx": int(row["idx"])}
        if self._cache is not None:
            self._cache[idx] = (self._copy_event(event), dict(meta))
        return event, meta


@register_dataset
def synthetic(**kwargs):
    return Synthetic(**kwargs)


class SyntheticTokens(DatasetBase):
    """Integer sequences for a token task: event ``idx`` is one document of
    ``trace_samples`` ids drawn Zipf (exponent 1) over ``vocab_size``, from
    ``default_rng(seed * 1e6 + idx)`` like :class:`Synthetic`. ``data`` is
    (1, L) int32 — the one "channel" is the ids — and no other field is
    set: nothing here is normalised, augmented or given soft labels
    (data/preprocess.py passes integer data through)."""

    _name = "synthetic_tokens"
    _part_range = None
    _channels = ["ids"]
    _sampling_rate = 1

    def __init__(
        self,
        *,
        num_events: int = 256,
        trace_samples: int = 8192,
        vocab_size: int = 16384,
        data_dir: str = "",
        cache: bool = False,
        **kwargs,
    ):
        del cache  # accepted for PackSource's sake; a draw is cheap
        self._num_events = num_events
        self._trace_samples = trace_samples
        ranks = np.arange(1, vocab_size + 1, dtype=np.float64)
        cdf = np.cumsum(1.0 / ranks)
        self._cdf = cdf / cdf[-1]
        super().__init__(data_dir=data_dir, **kwargs)

    def _load_meta_data(self) -> pd.DataFrame:
        meta = pd.DataFrame({"idx": np.arange(self._num_events)})
        return self._shuffle_and_split(meta)

    def _load_event_data(self, idx: int) -> Tuple[Event, dict]:
        row = self._meta_data.iloc[idx]
        rng = np.random.default_rng(int(self._seed) * 1_000_000 + int(row["idx"]))
        ids = np.searchsorted(self._cdf, rng.random(self._trace_samples))
        ids = np.minimum(ids, len(self._cdf) - 1).astype(np.int32)
        return {"data": ids[None, :]}, {"idx": int(row["idx"])}


@register_dataset
def synthetic_tokens(**kwargs):
    return SyntheticTokens(**kwargs)


@register_dataset
def synthetic_tokens_tiny(**kwargs):
    """The same over 256 ids: the vocabulary of the CPU-sized model preset
    (``models/nemotron_h.py`` TINY)."""
    return SyntheticTokens(**{"vocab_size": 256, **kwargs})
