"""Single-trace inference demo (ref demo_predict.py:26-97).

Load a checkpoint, normalize one 3-channel waveform, run the jitted forward,
and plot the phase-picking figure.

    python demo_predict.py --model-name seist_s_dpk --checkpoint <ckpt> \
        --input trace.npz --output-dir ./demo_out

``--input`` accepts an ``.npz`` with a ``(3, L)`` or ``(L, 3)`` ``data``
array; without it a synthetic event is generated so the demo always runs.
"""

from __future__ import annotations

import argparse

import jax
import numpy as np


def normalize(data: np.ndarray, mode: str = "std") -> np.ndarray:
    """Per-channel demean + scale (ref demo_predict.py:8-23) — delegates to
    the canonical seist_tpu.data.preprocess.normalize. The demo's 'max'
    historically meant abs-max (unlike the training pipeline's signed max),
    preserved via mode 'absmax'."""
    from seist_tpu.data.preprocess import normalize as _norm

    return _norm(data, "absmax" if mode == "max" else "std", axis=-1)


def load_data(path: str, in_samples: int) -> np.ndarray:
    if path:
        npz = np.load(path)
        data = np.asarray(npz["data"], dtype=np.float32)
        if data.shape[0] > data.shape[-1]:  # (L, C) -> (C, L)
            data = data.T
    else:
        from seist_tpu.data.synthetic import Synthetic

        ds = Synthetic(
            seed=0, mode="test", num_events=4, trace_samples=in_samples
        )
        data = ds[0][0]["data"]
    return data[:, :in_samples]


def main() -> None:
    parser = argparse.ArgumentParser(description="seist_tpu demo inference")
    parser.add_argument("--model-name", default="seist_s_dpk", type=str)
    parser.add_argument("--checkpoint", default="", type=str)
    parser.add_argument("--input", default="", type=str, help=".npz with 'data'")
    parser.add_argument("--in-samples", default=8192, type=int)
    parser.add_argument("--sampling-rate", default=50, type=int)
    parser.add_argument("--norm-mode", default="std", type=str)
    parser.add_argument("--output-dir", default="./demo_out", type=str)
    args = parser.parse_args()

    import seist_tpu
    from seist_tpu.models import api
    from seist_tpu.train.checkpoint import load_checkpoint
    from seist_tpu.utils.visualization import vis_phase_picking

    seist_tpu.load_all()

    model = api.create_model(
        args.model_name, in_channels=3, in_samples=args.in_samples
    )
    variables = api.init_variables(model, in_samples=args.in_samples, in_channels=3)
    if args.checkpoint:
        restored = load_checkpoint(args.checkpoint)
        variables = {"params": restored["params"]}
        stats = restored.get("batch_stats")
        if stats:  # omit the collection entirely for models without BN
            variables["batch_stats"] = stats

    data = normalize(load_data(args.input, args.in_samples), args.norm_mode)
    x = data.T[None, ...]  # (1, L, C) channels-last

    @jax.jit
    def forward(variables, x):
        return model.apply(variables, x, train=False)

    preds = np.asarray(forward(variables, x))[0]  # (L, 3)
    paths = vis_phase_picking(
        waveforms=data,
        waveforms_labels=["Z", "N", "E"],
        preds=preds.T,
        true_phase_idxs=[],
        true_phase_labels=[],
        pred_phase_labels=["Detection", "P-phase", "S-phase"],
        sampling_rate=args.sampling_rate,
        save_name=f"_{args.model_name}",
        save_dir=args.output_dir,
    )
    print(f"Saved: {paths}")


if __name__ == "__main__":
    main()
