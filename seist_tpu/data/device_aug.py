"""Device-side augmentation & soft-label synthesis — jit/vmap mirror of
the numpy :class:`~seist_tpu.data.preprocess.DataPreprocessor` hot path.

Why: bench r02-r04 all profiled the same shape — every training sample
crosses the host each step after *per-sample numpy* augmentation and
Python batch stacking, pinning the step at ~2.4% MFU with the chip idle
behind the input pipeline. This module moves the full train-time
preprocessing — window cut, event shift/add, noise-sample generation,
channel drop, amplitude scale, pre-emphasis, SNR noise, gaps,
normalization (signed-max / std semantics of ``preprocess.normalize``)
and soft-label curve synthesis — into the jitted train step, so the only
per-step host work left is (at most) a raw-row gather.

RNG contract (resume-stability)
-------------------------------
Every sample's randomness derives from ``(seed, epoch, index)`` only::

    key = fold_in(fold_in(PRNGKey(seed), epoch), index)

and each stochastic decision consumes a NAMED subkey
(``fold_in(key, TAG)``), never a positional stream. Named draws make the
consumption order-free: a sample is augmented identically whether it is
processed in step 3 of a fresh run or step 3 after a preempt/restore,
and independently of batch geometry, ``steps_per_call`` chunking, or
device count. (The host path's numpy analogue is
``default_rng(SeedSequence([seed, epoch, idx]))`` — same keying idea,
different generator, so host and device runs are each reproducible but
not bit-identical to each other.)

Batch-wide by construction
--------------------------
Every op here is written for ONE sample and ``vmap``ped over the batch,
and what ``vmap`` makes of a per-row ``lax.dynamic_slice`` /
``dynamic_update_slice`` is a gather / a scatter with a different start in
every row — which the TPU compiler expands into a ``while`` loop that
walks the batch ONE ROW an iteration, about 2 us each however few bytes
move. The soft labels were once placed that way (slice 26 samples out of a
padded buffer, add the window, write them back, per phase slot): twelve
loops, 3072 serial iterations and 6.3 of the region's 8.6 ms a step at
batch 256, to move 80 KB (PERF.md, PR 30). :func:`soft_label_place` is
therefore dense: each slot evaluates its curve over all the window's
columns from the one ``make_soft_window`` table, which the compiler fuses
into a few elementwise passes. Write the next op the same way: masks over
``jnp.arange(length)`` (as ``add_gaps``, ``generate_noise`` and
``add_event_once`` do) instead of a data-dependent slice. The per-row moves
that cannot be masks — ``shift_event``'s roll, ``cut_window``'s crop and
``add_event_once``'s roll, each a window of a circular row from a start of
its own — go through ``ops/row_window.circular_window``, whose batching rule
issues one Pallas kernel for the whole batch on the TPU (two such loops were
0.97 ms a step and a padded copy of the rows; PERF.md, PR 36) and the
``jnp.roll`` / ``dynamic_slice`` they were elsewhere.
``tests/test_chip_compile.py`` counts the region's ``while`` ops (none), so
a per-row slice shows up there before it shows up on a chip.

Golden parity
-------------
Integer draws are derived as ``low + min(floor(u * (high-low)),
high-low-1)`` computed in float32 on BOTH sides, so a device run's draws
can be replayed into the numpy ``DataPreprocessor`` exactly:
:func:`build_replay_script` walks the reference pipeline's documented
branch structure (preprocess.py:432-499 + 172-222) with the named draws
and emits the response queue a :class:`ScriptedRNG` feeds to
``DataPreprocessor.process`` — the golden parity suite
(tests/test_device_aug.py) asserts the device output matches the numpy
output within float tolerance, per-op and end-to-end.

Known tolerated deviations (documented, tested):

* float32 vs float64 accumulation order (normalize / SNR power) — rtol.
* coda boundaries ``int(spk + coda_ratio*(spk-ppk))`` are computed in
  f32 on device; a non-f32-exact ``coda_ratio`` (e.g. the reference's
  1.4) can land one sample off the f64 truncation near integer products.
* gate compares use f32 rates on device, f64 on host — divergence needs
  the drawn uniform to equal the rate's f32 rounding (p ~ 2^-24/gate).
"""

from __future__ import annotations

import dataclasses
from collections import deque
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from seist_tpu import taskspec
from seist_tpu.data.preprocess import (
    DataPreprocessor,
    make_soft_window,
    pad_phases,
)
from seist_tpu.ops.row_window import circular_window

# Invalid phase-slot sentinel: sorts after every real sample index.
_BIG = 2**30

# Named-draw tags (fold_in constants). Values are arbitrary but FROZEN:
# changing one silently re-randomizes every historical (seed, epoch, idx)
# augmentation stream.
_T_GEN_GATE = 1
_T_GEN_FIELD = 2
_T_ADD_GATE = 3
_T_ADD_TARGET = 4
_T_ADD_POS = 5
_T_ADD_SCALE = 6
_T_SHIFT_GATE = 7
_T_SHIFT = 8
_T_DROP_GATE = 9
_T_DROP_NUM = 10
_T_DROP_CH = 11
_T_SCALE_GATE = 12
_T_SCALE_FLIP = 13
_T_SCALE_FACTOR = 14
_T_PRE_GATE = 15
_T_NOISE_GATE = 16
_T_SNR = 17
_T_NOISE_FIELD = 18
_T_GAP_GATE = 19
_T_GAP_POS = 20
_T_GAP_START = 21
_T_GAP_END = 22
_T_CROP = 23

# SOFT io-items the device label synthesizer implements ('ppk+'/'spk+'
# and 'det+' are in the catalog but referenced by no model spec).
_SOFT_SUPPORTED = {"ppk", "spk", "non", "det"}


@dataclasses.dataclass(frozen=True)
class AugConfig:
    """Static (trace-time) configuration of the device pipeline. Field
    names/semantics match :class:`DataPreprocessor` constructor args."""

    seed: int
    window: int              # in_samples
    raw_len: int             # uniform raw trace length of the dataset
    channels: int
    phase_slots: int         # P: capacity of the phase arrays
    data_channels: Tuple[str, ...]
    sampling_rate: int
    norm_mode: str = "std"
    coda_ratio: float = 1.4
    min_event_gap: int = 0   # samples (DataPreprocessor.min_event_gap)
    max_event_num: int = 1
    add_event_rate: float = 0.0
    shift_event_rate: float = 0.0
    generate_noise_rate: float = 0.0
    drop_channel_rate: float = 0.0
    scale_amplitude_rate: float = 0.0
    pre_emphasis_rate: float = 0.0
    pre_emphasis_ratio: float = 0.97
    add_noise_rate: float = 0.0
    add_gap_rate: float = 0.0
    soft_label_shape: str = "gaussian"
    soft_label_width: int = 50

    @classmethod
    def from_preprocessor(
        cls,
        pre: DataPreprocessor,
        *,
        seed: int,
        raw_len: int,
        phase_slots: int,
    ) -> "AugConfig":
        return cls(
            seed=int(seed),
            window=int(pre.in_samples),
            raw_len=int(raw_len),
            channels=len(pre.data_channels),
            phase_slots=int(phase_slots),
            data_channels=tuple(pre.data_channels),
            sampling_rate=int(pre.sampling_rate),
            norm_mode=pre.norm_mode,
            coda_ratio=float(pre.coda_ratio),
            min_event_gap=int(pre.min_event_gap),
            max_event_num=int(pre._max_event_num),
            add_event_rate=float(pre.add_event_rate),
            shift_event_rate=float(pre.shift_event_rate),
            generate_noise_rate=float(pre.generate_noise_rate),
            drop_channel_rate=float(pre.drop_channel_rate),
            scale_amplitude_rate=float(pre.scale_amplitude_rate),
            pre_emphasis_rate=float(pre.pre_emphasis_rate),
            pre_emphasis_ratio=float(pre.pre_emphasis_ratio),
            add_noise_rate=float(pre.add_noise_rate),
            add_gap_rate=float(pre.add_gap_rate),
            soft_label_shape=pre.soft_label_shape,
            soft_label_width=int(pre.soft_label_width),
        )


# --------------------------------------------------------------------- draws
def sample_key(seed, epoch, idx) -> jax.Array:
    """Per-sample PRNG key — a pure function of (seed, epoch, idx)."""
    key = jax.random.PRNGKey(seed)
    key = jax.random.fold_in(key, epoch)
    return jax.random.fold_in(key, idx)


def _u2i(u, n):
    """``floor(u * n)`` clamped to ``[0, n-1]`` with the product computed
    in float32 — the ONE integer-draw formula shared (bit-exactly, via
    :func:`u2i_np`) with the host replay side."""
    n = jnp.asarray(n, jnp.int32)
    v = jnp.floor(u * n.astype(jnp.float32)).astype(jnp.int32)
    return jnp.minimum(v, n - 1)


def u2i_np(u, n: int) -> int:
    """Host mirror of :func:`_u2i` (same float32 product, same clamp)."""
    return min(int(np.float32(u) * np.float32(n)), int(n) - 1)


def draw_all(cfg: AugConfig, key: jax.Array) -> Dict[str, jax.Array]:
    """Every named draw of one sample, derived from its key. All uniforms
    are in [0, 1); fields are standard normal float32."""

    def u(tag, shape=()):
        return jax.random.uniform(
            jax.random.fold_in(key, tag), shape, jnp.float32
        )

    def norm(tag, shape):
        return jax.random.normal(
            jax.random.fold_in(key, tag), shape, jnp.float32
        )

    K = max(cfg.max_event_num, 1)
    C, L = cfg.channels, cfg.raw_len
    draws = {
        "gen_gate": u(_T_GEN_GATE),
        "add_gate": u(_T_ADD_GATE, (K,)),
        "add_target": u(_T_ADD_TARGET, (K,)),
        "add_pos": u(_T_ADD_POS, (K,)),
        "add_scale": u(_T_ADD_SCALE, (K,)),
        "shift_gate": u(_T_SHIFT_GATE),
        "shift_u": u(_T_SHIFT),
        "drop_gate": u(_T_DROP_GATE),
        "drop_num_u": u(_T_DROP_NUM),
        "drop_ch_u": u(_T_DROP_CH, (max(C - 1, 1),)),
        "scale_gate": u(_T_SCALE_GATE),
        "scale_flip": u(_T_SCALE_FLIP),
        "scale_factor_u": u(_T_SCALE_FACTOR),
        "pre_gate": u(_T_PRE_GATE),
        "noise_gate": u(_T_NOISE_GATE),
        "snr_u": u(_T_SNR, (C,)),
        "gap_gate": u(_T_GAP_GATE),
        "gap_pos_u": u(_T_GAP_POS),
        "gap_start_u": u(_T_GAP_START),
        "gap_end_u": u(_T_GAP_END),
        "crop_u": u(_T_CROP),
    }
    # The (C, L) normal fields are the expensive draws — only materialize
    # them when their op can actually fire (named keying means skipping
    # them cannot shift any other draw).
    if cfg.generate_noise_rate > 0:
        draws["gen_field"] = norm(_T_GEN_FIELD, (C, L))
    if cfg.add_noise_rate > 0:
        draws["noise_field"] = norm(_T_NOISE_FIELD, (C, L))
    return draws


# ----------------------------------------------------------------- phase ops
def _sorted_insert(vals, n, new):
    """Insert ``new`` at slot ``n`` of a sorted-valid-prefix array and
    re-sort (invalid slots hold _BIG and stay at the tail)."""
    P = vals.shape[0]
    return jnp.sort(jnp.where(jnp.arange(P) == n, new, vals))


def _coda_end(cfg: AugConfig, ppk, spk):
    """``int(spk + coda_ratio * (spk - ppk))`` — f32, trunc-toward-zero
    like python ``int()`` (astype truncates)."""
    v = spk.astype(jnp.float32) + jnp.float32(cfg.coda_ratio) * (
        spk - ppk
    ).astype(jnp.float32)
    return v.astype(jnp.int32)


# ------------------------------------------------------------- augment ops
def normalize(data, mode: str):
    """jnp mirror of ``preprocess.normalize`` (per-channel over the last
    axis): demean, then divide by the SIGNED max ('max' — the reference's
    training quirk), the std ('std'), or nothing ('')."""
    data = data - jnp.mean(data, axis=-1, keepdims=True)
    if mode == "":
        return data
    if mode == "max":
        scale = jnp.max(data, axis=-1, keepdims=True)
    elif mode == "std":
        scale = jnp.std(data, axis=-1, keepdims=True)
    else:
        raise ValueError(f"Supported modes: 'max', 'std', '', got '{mode}'")
    return data / jnp.where(scale == 0, 1.0, scale)


def generate_noise(cfg: AugConfig, data, ppks, np_p, spks, np_s, field):
    """Wipe every phase+coda span with white noise (ref preprocess.py:
    244-263). ``field`` is position-indexed: column ``t`` of the span gets
    ``field[:, t]`` — overlapping spans agree, matching numpy's sequential
    overwrite."""
    L = data.shape[-1]
    cols = jnp.arange(L)
    npair = jnp.minimum(np_p, np_s)
    for j in range(cfg.phase_slots):
        ppk, spk = ppks[j], spks[j]
        ce = jnp.clip(_coda_end(cfg, ppk, spk), 0, L)
        wipe = (j < npair) & (cols >= ppk) & (cols < ce)
        data = jnp.where(wipe[None, :], field, data)
    return data


def add_event_once(
    cfg: AugConfig, data, ppks, np_p, spks, np_s, u_t, u_pos, u_scale, active
):
    """One iteration of the event-duplication augment (ref preprocess.py:
    265-292): pick event ``floor(u_t * n)``, add a ``u_scale``-scaled copy
    at ``left + floor(u_pos * (right-left))`` when a slot exists."""
    L = data.shape[-1]
    j = _u2i(u_t, jnp.maximum(np_p, 1))
    ppk = jnp.take(ppks, j)
    spk = jnp.take(spks, j)
    ce = _coda_end(cfg, ppk, spk)
    left = ce + cfg.min_event_gap
    right = L - (spk - ppk) - cfg.min_event_gap
    fire = active & (np_p > 0) & (left < right)
    pos = left + _u2i(u_pos, jnp.maximum(right - left, 1))
    spk_add = pos + spk - ppk
    space = jnp.minimum(L - pos, ce - ppk)
    cols = jnp.arange(L)
    seg = (cols >= pos) & (cols < pos + space)
    rolled = circular_window(data, ppk - pos, L)  # roll by pos - ppk
    data = jnp.where(fire & seg[None, :], data + rolled * u_scale, data)
    ppks = jnp.where(fire, _sorted_insert(ppks, np_p, pos), ppks)
    spks = jnp.where(fire, _sorted_insert(spks, np_s, spk_add), spks)
    return data, ppks, np_p + fire, spks, np_s + fire


def shift_event(data, ppks, np_p, spks, np_s, shift):
    """Circular time shift (ref preprocess.py:294-305)."""
    L = data.shape[-1]
    P = ppks.shape[0]
    data = circular_window(data, -shift, L)  # roll by shift
    ar = jnp.arange(P)

    def sh(vals, n):
        return jnp.sort(jnp.where(ar < n, (vals + shift) % L, _BIG))

    return data, sh(ppks, np_p), np_p, sh(spks, np_s), np_s


def drop_channel(data, u_num, u_ch):
    """Zero ``1 + floor(u_num*(C-1))`` channels, chosen sequentially from
    the ascending remaining-candidate list (ref preprocess.py:307-321)."""
    C = data.shape[0]
    if C < 2:
        return data
    drop_num = 1 + _u2i(u_num, C - 1)
    cand = jnp.ones((C,), bool)
    chans = jnp.arange(C)
    for i in range(C - 1):
        active = i < drop_num
        k = _u2i(u_ch[i], C - i)
        rank = jnp.cumsum(cand) - 1
        sel = jnp.argmax((rank == k) & cand)
        hit = active & (chans == sel)
        data = jnp.where(hit[:, None], 0.0, data)
        cand = cand & ~hit
    return data


def adjust_amplitude(data):
    """Post-drop rescale by C / nonzero-channel-count (ref 323-333)."""
    max_amp = jnp.max(jnp.abs(data), axis=1)
    nnz = jnp.sum(max_amp != 0)
    factor = jnp.where(
        nnz > 0, data.shape[0] / jnp.maximum(nnz, 1).astype(jnp.float32), 1.0
    )
    return data * factor


def scale_amplitude(data, u_flip, u_factor):
    """x/÷ U(1,3) amplitude scale (ref preprocess.py:335-344)."""
    factor = 1.0 + 2.0 * u_factor
    return jnp.where(u_flip < 0.5, data * factor, data / factor)


def pre_emphasis(data, ratio: float):
    """First-order pre-emphasis filter (ref preprocess.py:346-353)."""
    return jnp.concatenate(
        [data[:, :1], data[:, 1:] - ratio * data[:, :-1]], axis=1
    )


def add_noise(data, u_snr, field):
    """Per-channel gaussian noise at SNR ``10 + floor(u*40)`` dB
    (ref preprocess.py:355-368)."""
    L = data.shape[-1]
    snr = 10 + _u2i(u_snr, 40)
    px = jnp.sum(data**2, axis=1) / L
    pn = px * 10.0 ** (-snr.astype(jnp.float32) / 10.0)
    return data + field * jnp.sqrt(pn)[:, None]


def add_gaps(data, ppks, np_p, spks, np_s, u_pos, u_start, u_end):
    """Zero a random span between phases (ref preprocess.py:370-390):
    unique sorted phases + (L-1), pick an inter-phase interval, zero a
    random sub-span of it."""
    L = data.shape[-1]
    P = ppks.shape[0]
    ar = jnp.arange(P)
    vals = jnp.concatenate(
        [
            jnp.where(ar < np_p, ppks, _BIG),
            jnp.where(ar < np_s, spks, _BIG),
            jnp.array([L - 1], jnp.int32),
        ]
    )
    vals = jnp.sort(vals)
    # set()-dedup: mark repeats invalid, re-sort so uniques pack the front.
    dup = jnp.concatenate([jnp.array([False]), vals[1:] == vals[:-1]])
    uniq = jnp.sort(jnp.where(dup, _BIG, vals))
    n_u = jnp.sum(uniq < _BIG).astype(jnp.int32)
    has = (np_p + np_s) > 0

    ip = _u2i(u_pos, jnp.maximum(n_u - 1, 1))
    lo = jnp.take(uniq, ip)
    hi = jnp.take(uniq, jnp.minimum(ip + 1, uniq.shape[0] - 1))
    sgt_p = lo + _u2i(u_start, jnp.maximum(hi - lo, 1))
    egt_p = sgt_p + _u2i(u_end, jnp.maximum(hi - sgt_p, 1))

    sgt_n = _u2i(u_start, L - 1)
    egt_n = sgt_n + 1 + _u2i(u_end, jnp.maximum(L - 1 - sgt_n, 1))

    sgt = jnp.where(has, sgt_p, sgt_n)
    egt = jnp.where(has, egt_p, egt_n)
    cols = jnp.arange(L)
    return jnp.where(((cols >= sgt) & (cols < egt))[None, :], 0.0, data)


def cut_window(cfg: AugConfig, data, ppks, np_p, spks, np_s, u_crop):
    """Cut the raw trace to ``cfg.window`` (ref preprocess.py:172-222,
    random-crop branch; the p_position_ratio mode is host-only). Shorter
    traces are zero-padded; equal lengths pass through — both draw-free,
    exactly like numpy."""
    L, W, P = cfg.raw_len, cfg.window, cfg.phase_slots
    C = data.shape[0]
    if L == W:
        return data, ppks, np_p, spks, np_s
    if L < W:
        pad = jnp.zeros((C, W - L), data.dtype)
        return jnp.concatenate([data, pad], axis=1), ppks, np_p, spks, np_s
    ar = jnp.arange(P)
    min_ppk = jnp.min(jnp.where(ar < np_p, ppks, _BIG))
    bound = jnp.maximum(
        jnp.minimum(min_ppk, L - W) - cfg.min_event_gap, 1
    )
    c_l = _u2i(u_crop, bound)
    win = circular_window(data, c_l, W)

    def cutp(vals, n):
        keep = (ar < n) & (vals >= c_l) & (vals < c_l + W)
        return (
            jnp.sort(jnp.where(keep, vals - c_l, _BIG)),
            jnp.sum(keep).astype(jnp.int32),
        )

    ppks2, np_p2 = cutp(ppks, np_p)
    spks2, np_s2 = cutp(spks, np_s)
    return win, ppks2, np_p2, spks2, np_s2


# ------------------------------------------------------------- soft labels
def pad_phases_dev(ppks, np_p, spks, np_s, padding_idx: int, num_samples):
    """Device mirror of ``preprocess.pad_phases`` positional pairing:
    returns 2P-slot arrays carrying the REAL sentinel values (-pad /
    num_samples+pad) plus the padded count."""
    P = ppks.shape[0]
    pad = abs(int(padding_idx))
    ar = jnp.arange(P)
    a, b = np_p, np_s
    # k = longest prefix with ppk[i] < spk[b-idx-1+i] for all i <= idx.
    cont = jnp.bool_(True)
    k = jnp.int32(0)
    for idx in range(P):
        sp_idx = jnp.clip(b - idx - 1 + ar, 0, P - 1)
        ok = jnp.all(
            jnp.where(ar <= idx, ppks < jnp.take(spks, sp_idx), True)
        )
        cont = cont & (idx < jnp.minimum(a, b)) & ok
        k = k + cont.astype(jnp.int32)
    n_lead = b - k            # sentinel ppks prepended
    n_tot = a + b - k
    i2 = jnp.arange(2 * P)
    ppks_pad = jnp.where(
        i2 < n_lead,
        -pad,
        jnp.take(ppks, jnp.clip(i2 - n_lead, 0, P - 1)),
    )
    spks_pad = jnp.where(
        i2 < b, jnp.take(spks, jnp.clip(i2, 0, P - 1)), num_samples + pad
    )
    return ppks_pad, spks_pad, n_tot


def soft_label_place(idxs, valid, window_arr, length: int):
    """Sum label windows centered at ``idxs`` (ref preprocess.py:567-619):
    out-of-range indices (idx < 0 or idx > length-1) contribute NOTHING
    (the reference skips them entirely, not partially); in-range windows
    are edge-cropped.

    Dense on purpose (module docstring, "Batch-wide by construction"):
    every slot evaluates its curve at all ``length`` columns from the one
    window table — column ``c`` reads entry ``c - (idx - left)`` where
    that lies in ``[0, width]`` and adds 0.0 elsewhere. Each sample is the
    same sum of the same float32 table entries in the same slot order as
    slicing and updating a padded buffer would give, bit for bit."""
    width = window_arr.shape[0] - 1
    left = width // 2
    wf = window_arr.astype(jnp.float32)
    cols = jnp.arange(length, dtype=jnp.int32)
    label = jnp.zeros((length,), jnp.float32)
    for j in range(idxs.shape[0]):
        idx = idxs[j]
        ok = valid[j] & (idx >= 0) & (idx <= length - 1)
        rel = cols - (idx - left)
        inside = ok & (rel >= 0) & (rel <= width)
        # mode="clip": the look-up clamps by itself; `inside` masks what
        # the clamp reads off either end of the table.
        label = label + jnp.where(inside, jnp.take(wf, rel, mode="clip"), 0.0)
    return label


def label_pick(cfg: AugConfig, vals, n, window_arr):
    """'ppk' / 'spk' soft label from the raw phase list."""
    valid = jnp.arange(cfg.phase_slots) < n
    return soft_label_place(vals, valid, window_arr, cfg.window)


def label_non(cfg: AugConfig, ppks, np_p, spks, np_s, window_arr):
    """'non' = 1 - soft(padded ppks) - soft(padded spks), clipped at 0."""
    W = cfg.window
    pp, ss, n_tot = pad_phases_dev(
        ppks, np_p, spks, np_s, cfg.soft_label_width, W
    )
    valid = jnp.arange(pp.shape[0]) < n_tot
    lbl = (
        1.0
        - soft_label_place(pp, valid, window_arr, W)
        - soft_label_place(ss, valid, window_arr, W)
    )
    return jnp.maximum(lbl, 0.0)


def label_det(cfg: AugConfig, ppks, np_p, spks, np_s, window_arr):
    """'det': per padded pair, soft windows at (ppk, coda-end) plus a 1.0
    fill over [clip(ppk), clip(coda-end)); summed and clipped at 1."""
    W = cfg.window
    pp, ss, n_tot = pad_phases_dev(
        ppks, np_p, spks, np_s, cfg.soft_label_width, W
    )
    cols = jnp.arange(W)
    label = jnp.zeros((W,), jnp.float32)
    for j in range(pp.shape[0]):
        ok = j < n_tot
        dst = pp[j]
        det = _coda_end(cfg, dst, ss[j])
        li = soft_label_place(
            jnp.stack([dst, det]),
            jnp.stack([ok, ok]),
            window_arr,
            W,
        )
        fill = ok & (cols >= jnp.clip(dst, 0, W)) & (cols < jnp.clip(det, 0, W))
        li = jnp.where(fill, 1.0, li)
        label = label + li
    return jnp.minimum(label, 1.0)


# ------------------------------------------------------------- composition
def process_event(cfg: AugConfig, data, ppks, np_p, spks, np_s, draws, augment):
    """Full train-time preprocessing of ONE event: augmentation (when
    ``augment``), window cut, normalization. Input phase arrays are the
    post-``_is_noise``/``pad_phases`` state the upload precomputed
    (both are draw-free and static per raw sample).

    Returns ``dict(win, ppks, np_p, spks, np_s, gen_fired)`` with ``win``
    the normalized ``(C, window)`` waveform and window-relative phases.
    """
    augment = jnp.asarray(augment, bool)

    def gate(name, rate):
        return augment & (draws[name] < jnp.float32(rate))

    # Every op below is guarded by a TRACE-time `cfg.rate > 0` check:
    # rates are static, so a disabled op costs nothing in the compiled
    # program (XLA cannot fold `u < 0.0` selects away by itself, and the
    # (C, L) noise fields in particular are real work). Named draw keying
    # makes the elision stream-invariant for the enabled ops.

    # -- generate-noise branch (ref 418-425): wipe, clear, drop?, scale?
    if cfg.generate_noise_rate > 0:
        gen_fired = gate("gen_gate", cfg.generate_noise_rate)
        gdata = generate_noise(
            cfg, data, ppks, np_p, spks, np_s, draws["gen_field"]
        )
        if cfg.drop_channel_rate > 0:
            g_drop = gate("drop_gate", cfg.drop_channel_rate)
            gd = adjust_amplitude(
                drop_channel(gdata, draws["drop_num_u"], draws["drop_ch_u"])
            )
            gdata = jnp.where(g_drop, gd, gdata)
        if cfg.scale_amplitude_rate > 0:
            g_scale = gate("scale_gate", cfg.scale_amplitude_rate)
            gdata = jnp.where(
                g_scale,
                scale_amplitude(
                    gdata, draws["scale_flip"], draws["scale_factor_u"]
                ),
                gdata,
            )
    else:
        gen_fired = jnp.zeros((), bool)

    # -- regular branch (ref 426-444): add*, shift?, drop?, scale?, pre?,
    # noise?, gap?
    e, epp, enp, ess, ens = data, ppks, np_p, spks, np_s
    n0 = np_p
    if cfg.add_event_rate > 0:
        for i in range(cfg.max_event_num):
            act = (
                augment
                & (i < cfg.max_event_num - n0)
                & (draws["add_gate"][i] < jnp.float32(cfg.add_event_rate))
            )
            e, epp, enp, ess, ens = add_event_once(
                cfg, e, epp, enp, ess, ens,
                draws["add_target"][i], draws["add_pos"][i],
                draws["add_scale"][i], act,
            )
    if cfg.shift_event_rate > 0:
        sh_fire = gate("shift_gate", cfg.shift_event_rate)
        shift = _u2i(draws["shift_u"], cfg.raw_len)
        se, sepp, _, sess, _ = shift_event(e, epp, enp, ess, ens, shift)
        e = jnp.where(sh_fire, se, e)
        epp = jnp.where(sh_fire, sepp, epp)
        ess = jnp.where(sh_fire, sess, ess)
    if cfg.drop_channel_rate > 0:
        d_fire = gate("drop_gate", cfg.drop_channel_rate)
        de = adjust_amplitude(
            drop_channel(e, draws["drop_num_u"], draws["drop_ch_u"])
        )
        e = jnp.where(d_fire, de, e)
    if cfg.scale_amplitude_rate > 0:
        s_fire = gate("scale_gate", cfg.scale_amplitude_rate)
        e = jnp.where(
            s_fire,
            scale_amplitude(e, draws["scale_flip"], draws["scale_factor_u"]),
            e,
        )
    if cfg.pre_emphasis_rate > 0:
        p_fire = gate("pre_gate", cfg.pre_emphasis_rate)
        e = jnp.where(p_fire, pre_emphasis(e, cfg.pre_emphasis_ratio), e)
    if cfg.add_noise_rate > 0:
        n_fire = gate("noise_gate", cfg.add_noise_rate)
        e = jnp.where(
            n_fire, add_noise(e, draws["snr_u"], draws["noise_field"]), e
        )
    if cfg.add_gap_rate > 0:
        gp_fire = gate("gap_gate", cfg.add_gap_rate)
        e = jnp.where(
            gp_fire,
            add_gaps(
                e, epp, enp, ess, ens,
                draws["gap_pos_u"], draws["gap_start_u"], draws["gap_end_u"],
            ),
            e,
        )

    # -- branch select (non-augmented samples fall through untouched:
    # every gate above is &augment).
    if cfg.generate_noise_rate > 0:
        data = jnp.where(gen_fired, gdata, e)
        big = jnp.full_like(ppks, _BIG)
        ppks = jnp.where(gen_fired, big, epp)
        spks = jnp.where(gen_fired, big, ess)
        np_p = jnp.where(gen_fired, 0, enp)
        np_s = jnp.where(gen_fired, 0, ens)
    else:
        data, ppks, spks, np_p, np_s = e, epp, ess, enp, ens

    win, ppks, np_p, spks, np_s = cut_window(
        cfg, data, ppks, np_p, spks, np_s, draws["crop_u"]
    )
    win = normalize(win, cfg.norm_mode)
    return {
        "win": win,
        "ppks": ppks,
        "np_p": np_p,
        "spks": spks,
        "np_s": np_s,
        "gen_fired": gen_fired,
    }


def _soft_item(cfg: AugConfig, name: str, proc, window_arr):
    if name == "ppk":
        return label_pick(cfg, proc["ppks"], proc["np_p"], window_arr)
    if name == "spk":
        return label_pick(cfg, proc["spks"], proc["np_s"], window_arr)
    if name == "non":
        return label_non(
            cfg, proc["ppks"], proc["np_p"], proc["spks"], proc["np_s"],
            window_arr,
        )
    if name == "det":
        return label_det(
            cfg, proc["ppks"], proc["np_p"], proc["spks"], proc["np_s"],
            window_arr,
        )
    if name in cfg.data_channels:
        return proc["win"][cfg.data_channels.index(name)]
    if name in [f"d{c}" for c in cfg.data_channels]:
        ch = proc["win"][cfg.data_channels.index(name[-1])]
        return jnp.concatenate([jnp.zeros((1,), ch.dtype), jnp.diff(ch)])
    raise NotImplementedError(f"device-aug: unsupported soft item '{name}'")


def assemble_io(cfg: AugConfig, names, proc, values, onehots, window_arr):
    """Device mirror of ``DataPreprocessor.get_inputs`` /
    ``get_targets_for_loss``: grouped names stack channels-last; the
    waveform group is the processed window transposed to (L, C)."""
    items = []
    for name in names:
        if isinstance(name, (tuple, list)):
            if tuple(name) == tuple(cfg.data_channels):
                items.append(proc["win"].T)
            else:
                items.append(
                    jnp.stack(
                        [_soft_item(cfg, sub, proc, window_arr) for sub in name],
                        axis=-1,
                    )
                )
            continue
        kind = taskspec.get_kind(name)
        if kind == taskspec.SOFT:
            items.append(_soft_item(cfg, name, proc, window_arr))
        elif kind == taskspec.VALUE:
            # generate_noise clears value fields (ref _clear_event_except).
            items.append(
                jnp.where(proc["gen_fired"], 0.0, values[name])
            )
        elif kind == taskspec.ONEHOT:
            nc = taskspec.get_num_classes(name)
            items.append(
                jax.nn.one_hot(onehots[name], nc, dtype=jnp.int32)
            )
        else:  # pragma: no cover - catalog has exactly three kinds
            raise NotImplementedError(name)
    return tuple(items) if len(items) > 1 else items[0]


def make_row_processor(cfg: AugConfig, input_names, label_names):
    """Build ``process(rows, idx, aug, epoch) -> (inputs, loss_targets)``
    — the vmapped per-batch device preprocessing used INSIDE the jitted
    train step. ``rows`` is the raw-row pytree (see pipeline.RawStore),
    ``idx`` the (B,) global epoch indices keying the RNG, ``aug`` the
    (B,) augment flags (2x-epoch rule), ``epoch`` a scalar."""
    window_arr = jnp.asarray(
        make_soft_window(cfg.soft_label_width, cfg.soft_label_shape),
        jnp.float32,
    )

    def one(row, idx, aug, epoch):
        key = sample_key(cfg.seed, epoch, idx)
        draws = draw_all(cfg, key)
        proc = process_event(
            cfg, row["data"], row["ppks"], row["np_p"], row["spks"],
            row["np_s"], draws, aug,
        )
        values = row.get("values", {})
        onehots = row.get("onehots", {})
        inputs = assemble_io(cfg, input_names, proc, values, onehots, window_arr)
        targets = assemble_io(cfg, label_names, proc, values, onehots, window_arr)
        return inputs, targets

    def process(rows, idx, aug, epoch):
        # Region scope (obs/scopes.py): metadata only, names the device
        # ops of augmentation and label synthesis in a trace.
        with jax.named_scope("device_aug"):
            return jax.vmap(lambda r, i, a: one(r, i, a, epoch))(
                rows, idx, aug
            )

    return process


def make_cache_processor(
    cfg: AugConfig, input_names, label_names, n_raw: int, augmentation: bool
):
    """Cache-resident variant: ``process(cache, idx, epoch)`` gathers the
    raw rows from the HBM-resident store by ``idx % n_raw`` (the 2x-epoch
    rule maps ``idx >= n_raw`` to the augmented replica) and runs the
    row processor — zero per-step host involvement beyond the tiny idx
    upload."""
    row_proc = make_row_processor(cfg, input_names, label_names)

    def process(cache, idx, epoch):
        if augmentation:
            raw_idx = idx % n_raw
            aug = idx >= n_raw
        else:
            raw_idx = idx
            aug = jnp.zeros(idx.shape, bool)
        with jax.named_scope("cache_gather"):
            rows = jax.tree.map(
                lambda a: jnp.take(a, raw_idx, axis=0), cache
            )
        # RNG keys use the GLOBAL epoch index (matching the host path's
        # SeedSequence([seed, epoch, idx])), so the raw and augmented
        # replicas of a sample draw from different streams.
        return row_proc(rows, idx, aug, epoch)

    return process


# ------------------------------------------------------- support / fallback
def unsupported_reasons(
    pre: DataPreprocessor, input_names, label_names
) -> List[str]:
    """Config features the device pipeline does not implement (the worker
    falls back to the host path and logs these)."""
    reasons = []
    if pre.mask_percent > 0 or pre.noise_percent > 0:
        reasons.append("mask_percent/noise_percent window masking")
    if 0 <= pre.p_position_ratio <= 1:
        reasons.append("p_position_ratio pinned-P windowing")
    if pre.norm_mode not in ("std", "max", ""):
        reasons.append(f"norm_mode '{pre.norm_mode}'")
    names = taskspec.flatten_io_names(list(input_names) + list(label_names))
    diff_names = {f"d{c}" for c in pre.data_channels}
    for name in names:
        kind = taskspec.get_kind(name)
        if kind == taskspec.SOFT and name not in (
            _SOFT_SUPPORTED | set(pre.data_channels) | diff_names
        ):
            reasons.append(f"soft io-item '{name}'")
        if kind in (taskspec.VALUE, taskspec.ONEHOT) and (
            pre.generate_noise_rate > 0
        ):
            # The host path CRASHES here (cleared value lists stack as
            # shape-(0,)); refuse rather than invent semantics.
            reasons.append(
                f"generate_noise_rate > 0 with {kind} label '{name}'"
            )
    return reasons


def hbm_budget_bytes(explicit_gb: float = 0.0) -> int:
    """HBM budget for the resident epoch cache: an explicit --device-aug-
    hbm-gb wins; otherwise half the device's reported ``bytes_limit``. An
    accelerator that reports no limit is an error (pass the flag). The CPU
    backend has no HBM and reports no stats: its resident "device" arrays
    live in host RAM, budgeted at a nominal 4 GiB."""
    if explicit_gb and explicit_gb > 0:
        return int(explicit_gb * (1 << 30))
    dev = jax.local_devices()[0]
    if dev.platform == "cpu":
        return 4 << 30
    limit = int((dev.memory_stats() or {}).get("bytes_limit", 0))
    if limit <= 0:
        raise RuntimeError(
            f"{dev.device_kind} reports no memory limit (memory_stats() "
            f"has no bytes_limit): pass --device-aug-hbm-gb explicitly"
        )
    return limit // 2


def select_device_aug_mode(
    requested: str,
    est_bytes: int,
    budget_bytes: int,
    reasons: Sequence[str],
) -> Tuple[str, str]:
    """Resolve the effective --device-aug mode with automatic fallback:
    unsupported config -> 'off' (host path); 'cached' over the HBM budget
    -> 'step' (device aug, host-fed raw rows). Returns (mode, reason).

    Multi-host runs no longer force the step fallback: the cache places
    each host's addressable sample-axis slices itself
    (``pipeline.DeviceEpochCache``) and the epoch index stream is
    host-sharded under the same deterministic global shard contract as
    the host Loader (``epoch_index_chunks(num_shards=, shard_index=)``)
    — the invariant the old fallback existed to protect."""
    if requested not in ("off", "step", "cached"):
        raise ValueError(f"--device-aug must be off|step|cached, got '{requested}'")
    if requested == "off":
        return "off", ""
    if reasons:
        return "off", "unsupported by device pipeline: " + "; ".join(reasons)
    if requested == "cached":
        if est_bytes > budget_bytes:
            return "step", (
                f"epoch cache ~{est_bytes / 2**20:.0f} MiB exceeds HBM "
                f"budget {budget_bytes / 2**20:.0f} MiB"
            )
        return "cached", ""
    return "step", ""


# ----------------------------------------------------------- golden parity
class ScriptedRNG:
    """``np.random.Generator`` stand-in replaying a prepared response
    queue — the injection side of the golden parity suite. Raises on any
    call-kind mismatch, so a branch misprediction in the replay script
    fails loudly instead of silently desynchronizing."""

    def __init__(self, script: Sequence[Tuple[str, Any]]):
        self._q = deque(script)

    def _pop(self, kind: str):
        if not self._q:
            raise AssertionError(f"replay script exhausted at '{kind}' call")
        k, v = self._q.popleft()
        if k != kind:
            raise AssertionError(f"replay script expected '{k}', got '{kind}'")
        return v

    def random(self) -> float:
        return float(self._pop("random"))

    def integers(self, low, high=None) -> int:
        v = int(self._pop("integers"))
        lo, hi = (0, low) if high is None else (low, high)
        if not lo <= v < hi:
            raise AssertionError(f"scripted int {v} outside [{lo}, {hi})")
        return v

    def uniform(self, low=0.0, high=1.0) -> float:
        return float(self._pop("uniform"))

    def standard_normal(self, shape):
        v = np.asarray(self._pop("normal"), np.float32)
        want = (shape,) if isinstance(shape, (int, np.integer)) else tuple(shape)
        if v.shape != want:
            raise AssertionError(f"scripted normal {v.shape} != asked {want}")
        return v

    def choice(self, seq, size=None, replace=True):
        return self._pop("choice")

    def assert_exhausted(self) -> None:
        if self._q:
            raise AssertionError(f"{len(self._q)} scripted draws unconsumed")


def build_replay_script(
    pre: DataPreprocessor, event: dict, draws: dict, augmentation: bool = True
) -> List[Tuple[str, Any]]:
    """Translate one sample's named device draws into the numpy
    ``DataPreprocessor.process`` consumption order. This walks the
    reference pipeline's branch structure (preprocess.py:432-499 +
    172-222) with shadow phase bookkeeping; the real numpy code still
    computes every result — a branch mismatch surfaces as a ScriptedRNG
    kind error, never as silent desync."""
    data = np.asarray(event["data"])
    C, L = data.shape
    d = {k: np.asarray(v) for k, v in draws.items()}
    ppks, spks = list(event["ppks"]), list(event["spks"])
    if pre._is_noise(data, ppks, spks, event["snr"]):
        ppks, spks = [], []
    ppks, spks = pad_phases(ppks, spks, pre.min_event_gap, pre.in_samples)
    q: List[Tuple[str, Any]] = []

    def gate(name, rate):
        u = float(d[name])
        q.append(("random", u))
        return u < rate

    def drop_block():
        if C < 2:
            return
        drop_num = 1 + u2i_np(d["drop_num_u"], C - 1)
        q.append(("choice", drop_num))
        cands = list(range(C))
        for i in range(drop_num):
            c = cands[u2i_np(d["drop_ch_u"][i], len(cands))]
            q.append(("choice", c))
            cands.remove(c)

    def scale_block():
        q.append(("uniform", float(d["scale_flip"])))
        q.append(("uniform", 1.0 + 2.0 * float(d["scale_factor_u"])))

    if augmentation:
        if pre.mask_percent > 0 or pre.noise_percent > 0:
            raise NotImplementedError(
                "mask/noise window augments are host-only"
            )
        if gate("gen_gate", pre.generate_noise_rate):
            for ppk, spk in zip(ppks, spks):
                ce = int(
                    np.clip(int(spk + pre.coda_ratio * (spk - ppk)), 0, L)
                )
                if ppk < ce:
                    q.append(("normal", d["gen_field"][:, ppk:ce]))
            ppks, spks = [], []
            if gate("drop_gate", pre.drop_channel_rate):
                drop_block()
            if gate("scale_gate", pre.scale_amplitude_rate):
                scale_block()
        else:
            n0 = len(ppks)
            for i in range(max(0, pre._max_event_num - n0)):
                u = float(d["add_gate"][i])
                q.append(("random", u))
                if u < pre.add_event_rate and ppks:
                    t = u2i_np(d["add_target"][i], len(ppks))
                    q.append(("integers", t))
                    ppk, spk = ppks[t], spks[t]
                    ce = int(spk + pre.coda_ratio * (spk - ppk))
                    left = ce + pre.min_event_gap
                    right = L - (spk - ppk) - pre.min_event_gap
                    if left < right:
                        pos = left + u2i_np(d["add_pos"][i], right - left)
                        q.append(("integers", pos))
                        q.append(("random", float(d["add_scale"][i])))
                        ppks.append(pos)
                        spks.append(pos + spk - ppk)
                    ppks.sort()
                    spks.sort()
            if gate("shift_gate", pre.shift_event_rate):
                s = u2i_np(d["shift_u"], L)
                q.append(("integers", s))
                ppks = sorted((p + s) % L for p in ppks)
                spks = sorted((x + s) % L for x in spks)
            if gate("drop_gate", pre.drop_channel_rate):
                drop_block()
            if gate("scale_gate", pre.scale_amplitude_rate):
                scale_block()
            gate("pre_gate", pre.pre_emphasis_rate)
            if gate("noise_gate", pre.add_noise_rate):
                for c in range(C):
                    snr = 10 + u2i_np(d["snr_u"][c], 40)
                    q.append(("integers", snr))
                    q.append(("normal", d["noise_field"][c]))
            if gate("gap_gate", pre.add_gap_rate):
                phases = sorted(ppks + spks)
                if len(phases) > 0:
                    phases.append(L - 1)
                    phases = sorted(set(phases))
                    ip = u2i_np(d["gap_pos_u"], len(phases) - 1)
                    q.append(("integers", ip))
                    sgt = phases[ip] + u2i_np(
                        d["gap_start_u"], phases[ip + 1] - phases[ip]
                    )
                    q.append(("integers", sgt))
                    egt = sgt + u2i_np(d["gap_end_u"], phases[ip + 1] - sgt)
                    q.append(("integers", egt))
                else:
                    sgt = u2i_np(d["gap_start_u"], L - 1)
                    q.append(("integers", sgt))
                    egt = sgt + 1 + u2i_np(d["gap_end_u"], L - 1 - sgt)
                    q.append(("integers", egt))

    if L > pre.in_samples:
        bound = max(min(ppks + [L - pre.in_samples]) - pre.min_event_gap, 1)
        q.append(("integers", u2i_np(d["crop_u"], bound)))
    return q


def make_replay_rng(
    pre: DataPreprocessor, event: dict, draws: dict, augmentation: bool = True
) -> ScriptedRNG:
    """ScriptedRNG that makes ``pre.process(event, augmentation, rng=...)``
    consume exactly the device pipeline's named draws."""
    return ScriptedRNG(build_replay_script(pre, event, draws, augmentation))


def host_prepare(
    pre: DataPreprocessor, event: dict, phase_slots: int
) -> Dict[str, Any]:
    """The draw-free host half of the device pipeline, applied ONCE at
    upload: ``_is_noise`` classification (clearing noise traces' labels)
    and ``pad_phases`` — both static per raw sample. Returns the fixed-
    shape row dict the device processor consumes."""
    data = np.ascontiguousarray(np.asarray(event["data"], np.float32))
    ppks, spks = list(event["ppks"]), list(event["spks"])
    is_noise = pre._is_noise(data, ppks, spks, event["snr"])
    if is_noise:
        ppks, spks = [], []
    ppks, spks = pad_phases(ppks, spks, pre.min_event_gap, pre.in_samples)
    if max(len(ppks), len(spks)) > phase_slots:
        raise ValueError(
            f"event has {max(len(ppks), len(spks))} phases > "
            f"phase_slots {phase_slots}"
        )

    def arr(vals):
        return np.asarray(
            list(vals) + [_BIG] * (phase_slots - len(vals)), np.int32
        )

    return {
        "data": data,
        "ppks": arr(ppks),
        "np_p": np.int32(len(ppks)),
        "spks": arr(spks),
        "np_s": np.int32(len(spks)),
        "is_noise": bool(is_noise),
    }
