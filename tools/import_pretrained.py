"""Import a reference torch checkpoint (.pth) into an orbax checkpoint.

User-facing path for the reference's 18 published SeisT weights
(``/root/reference/pretrained/*.pth``, download table ref README.md:136-184):
convert the raw torch state-dict (layout mapping in tools/parity.py) and
write a params+batch_stats orbax checkpoint that ``--checkpoint`` (test
mode / resume) and ``demo_predict.py`` consume directly.

    python tools/import_pretrained.py \
        --pth /root/reference/pretrained/seist_s_dpk_diting.pth \
        --model-name seist_s_dpk --out ./imported/seist_s_dpk

Then:

    python demo_predict.py --model-name seist_s_dpk \
        --checkpoint ./imported/seist_s_dpk
"""

from __future__ import annotations

import argparse
import os
import sys

_TOOLS = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _TOOLS)  # for `parity`
sys.path.insert(0, os.path.dirname(_TOOLS))  # for `seist_tpu` without install


def main() -> None:
    parser = argparse.ArgumentParser(
        description="torch .pth -> orbax checkpoint importer"
    )
    parser.add_argument("--pth", required=True, type=str,
                        help="path to the torch state-dict (.pth)")
    parser.add_argument("--model-name", required=True, type=str,
                        help="registered model name, e.g. seist_s_dpk")
    parser.add_argument("--in-samples", default=8192, type=int)
    parser.add_argument("--in-channels", default=None, type=int,
                        help="default: the model's task-spec input count "
                        "(3 for most, 2 for ditingmotion's [z, dz])")
    parser.add_argument("--out", required=True, type=str,
                        help="output orbax checkpoint directory")
    args = parser.parse_args()

    # Pure host-side conversion (shape-only trace + numpy + orbax): force
    # the CPU backend — a converter has no business holding the chip.
    import jax

    jax.config.update("jax_platforms", "cpu")

    import torch

    import seist_tpu
    from parity import convert_state_dict
    from seist_tpu.models import api

    seist_tpu.load_all()

    sd = torch.load(args.pth, map_location="cpu", weights_only=True)
    # The shipped .pth files are raw state-dicts; full training checkpoints
    # nest the weights under 'model_dict' (ref _factory.py:59-87,101-102).
    if "model_dict" in sd:
        sd = sd["model_dict"]
    sd = {
        k.removeprefix("module.").removeprefix("_orig_mod."): v
        for k, v in sd.items()
    }

    if args.in_channels is None:
        from seist_tpu import taskspec

        try:
            args.in_channels = taskspec.get_num_inchannels(args.model_name)
        except KeyError:
            # distpt_network has no task spec (ref ships its config
            # commented out); every spec-less model takes 3-channel input.
            args.in_channels = 3

    model = api.create_model(
        args.model_name,
        in_channels=args.in_channels,
        in_samples=args.in_samples,
    )
    shapes = api.param_shapes(
        model, in_samples=args.in_samples, in_channels=args.in_channels
    )
    converted = convert_state_dict(sd, shapes)

    import orbax.checkpoint as ocp

    payload = {
        "params": converted["params"],
        "batch_stats": converted.get("batch_stats", {}),
        "meta": {"epoch": -1, "loss": float("inf"), "step": 0},
    }
    out = os.path.abspath(args.out)
    with ocp.StandardCheckpointer() as saver:
        saver.save(out, payload, force=True)
    n = sum(
        int(v.size)
        for v in __import__("jax").tree_util.tree_leaves(payload["params"])
    )
    print(f"Imported {args.pth} -> {out} ({n:,} params)")


if __name__ == "__main__":
    main()
