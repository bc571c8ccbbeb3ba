"""Device time of every executable other than the decode step, per
admission in the traced window (engine layer): mostly the batch-1 packed
prefill, with the cache insert, the slot scrub and the first-token sampler,
which the trace cannot yet tell apart.  Moves ``ttft_p50_s``.
"""


def read(ctx):
    admissions = len(ctx["uids"])
    t = ctx["trace"].module_time_except(
        ctx["patterns"]["modules"]["decode_step"])
    return 1e3 * t / admissions if admissions and t > 0 else None
