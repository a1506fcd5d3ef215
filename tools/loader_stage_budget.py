"""Per-stage cost budget of the input pipeline, in ms per waveform.

Times each loader stage in isolation on the real-format reader path
("publish a per-stage cost breakdown that lets a reader
verify the claim"):

  read      — dataset reader: h5py waveform read + metadata row
  augment   — DataPreprocessor.process with augmentation (window, the nine
              augmentations, normalize)
  labels    — soft-label + metrics-target generation
  assembly  — np.stack of a full batch + meta json

Prints one JSON line with ms/wf per stage and the implied serial wf/s.

    python tools/loader_stage_budget.py [n_samples] [batch]

Env: BENCH_DATASET (diting_light | synthetic | packed), BENCH_SAMPLES (8192).
``packed`` measures the packed-shard repack of the SAME diting_light
fixture (tools/pack_dataset.py): the read-stage delta vs diting_light is
the h5py per-sample API tax the offline repack removes.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def main() -> None:
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 600
    batch = int(sys.argv[2]) if len(sys.argv) > 2 else 200
    in_samples = int(os.environ.get("BENCH_SAMPLES", 8192))
    dataset_name = os.environ.get("BENCH_DATASET", "diting_light")

    import numpy as np

    import seist_tpu
    from seist_tpu import taskspec
    from seist_tpu.data import pipeline

    seist_tpu.load_all()
    spec = taskspec.get_task_spec("seist_l_dpk")
    ds_kw: dict = {}
    data_dir = ""
    if dataset_name == "synthetic":
        ds_kw = {"num_events": max(512, n)}
    elif dataset_name == "packed":
        # The packed-shard repack of the SAME fixture:
        # read-stage delta vs diting_light is the measured h5py tax.
        from tools.fixtures import ensure_packed_fixture

        data_dir = ensure_packed_fixture(max(1000, n), in_samples)
    else:
        from tools.fixtures import write_diting_light_fixture

        n_events = max(1000, n)
        data_dir = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            os.pardir,
            "logs",
            f"loader_fixture_{n_events}x{in_samples}",
        )
        marker = os.path.join(data_dir, ".complete")
        if not os.path.exists(marker):
            write_diting_light_fixture(
                data_dir, n_events=n_events, trace_samples=in_samples
            )
            with open(marker, "w") as f:
                f.write("ok\n")

    ds = pipeline.from_task_spec(
        spec,
        dataset_name,
        "train",
        seed=0,
        in_samples=in_samples,
        augmentation=True,
        data_dir=data_dir,
        dataset_kwargs=ds_kw,
    )
    reader = ds._dataset
    pre = ds.preprocessor
    size = len(reader)
    idxs = [i % size for i in range(n)]

    # Warm caches (h5 handles, soft-label windows, native dlopen).
    for i in idxs[:20]:
        ds[i]

    def timed(fn, items):
        t0 = time.perf_counter()
        out = [fn(x) for x in items]
        return (time.perf_counter() - t0) / len(items) * 1e3, out

    # read
    ms_read, events = timed(lambda i: reader[i], idxs)

    # augment (process mutates a copy; per-sample rng like the real path)
    def aug(pair):
        i, (event, _meta) = pair
        rng = np.random.default_rng(np.random.SeedSequence([0, 0, i]))
        return pre.process(event=dict(event), augmentation=True, rng=rng)

    ms_aug, processed = timed(aug, list(enumerate(events)))

    # labels
    def labels(event):
        inputs = pre.get_inputs(event, ds._input_names)
        lt = pre.get_targets_for_loss(event, ds._label_names)
        mt = pre.get_targets_for_metrics(
            event, max_event_num=1, task_names=ds._task_names
        )
        return inputs, lt, mt

    ms_labels, samples = timed(labels, processed)

    # assembly (stack into batches + meta json, as Loader.__iter__ does)
    metas = [m for _, m in events]

    def assemble(lo):
        part = samples[lo : lo + batch]
        inputs = pipeline._stack([s[0] for s in part])
        lt = pipeline._stack([s[1] for s in part])
        mt = {k: np.stack([s[2][k] for s in part]) for k in part[0][2]}
        mj = [
            json.dumps({k: str(v) for k, v in dict(m).items()})
            for m in metas[lo : lo + batch]
        ]
        return inputs, lt, mt, mj

    starts = list(range(0, n - batch + 1, batch)) or [0]
    t0 = time.perf_counter()
    for lo in starts:
        assemble(lo)
    ms_asm = (time.perf_counter() - t0) / (len(starts) * batch) * 1e3

    total = ms_read + ms_aug + ms_labels + ms_asm
    print(
        json.dumps(
            {
                "metric": "loader_stage_budget",
                "unit": "ms/waveform",
                "dataset": dataset_name,
                "in_samples": in_samples,
                "read": round(ms_read, 3),
                "augment": round(ms_aug, 3),
                "labels": round(ms_labels, 3),
                "assembly": round(ms_asm, 3),
                "total": round(total, 3),
                "implied_serial_wfs": round(1e3 / total, 1),
            }
        )
    )


if __name__ == "__main__":
    main()
