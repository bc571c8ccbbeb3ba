"""Device time of the batch-1 packed prefill per pack (model prefill layer,
``models/model.prefill``).

Sum of the traced window's runs of the prefill executable (the module the
program names ``jit_prefill``) / the packs the window's admissions
prefilled (each admitted prompt's length // the pack).  The engine
prefills between decode ticks, so every slot waits on it: moves
``out_tok_s``.  Nothing is read from a program whose prefill module has
another name.
"""

PREFILL = [r"^jit_prefill\("]


def read(ctx):
    t, n = ctx["trace"].time_of(PREFILL, modules=True)
    pack = ctx["cfg"]["lpsa"]["pack"]
    packs = sum(ctx["loop"].log[u]["prompt_len"] // pack
                for u in ctx["uids"])
    return 1e3 * t / packs if n and packs else None
