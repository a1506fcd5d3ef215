"""Model FLOP/s utilisation (%): the operations the forward and backward
passes require per waveform (counted from shapes, stored in the
configuration file) x waveforms per second of the window / (chips x peak).
An end-to-end utilisation, not a roofline share of any kernel."""


def read(record, args, ctx):
    per_wf = (ctx.config.get("flops_per_waveform") or {}).get(args["which"])
    if not per_wf or not record.get("window_s"):
        return None
    rate = record["waveforms"] / record["window_s"]
    peak = ctx.device["peaks"]["bf16_flops"] * int(ctx.cell["chips"])
    return 100.0 * per_wf * rate / peak
