"""Device-busy time (ms) per optimizer step: the union of the device's op
intervals inside the slice's step frame / the whole steps the frame holds
(device trace, ``trace_reduce.step_frame``; the slice opens with the window,
inside an epoch, so only the step runs. What a call does once, before its
first step, lies outside the frame and shows in the breakdown)."""


def read(record, args, ctx):
    frame = (record.get("trace") or {}).get("frame")
    if not frame:
        return None
    return 1e3 * frame["busy_per_step_s"]
