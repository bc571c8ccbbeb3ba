"""Device time of the decode-step executable per tick (engine layer).

Sum of the traced window's runs of the engine's decode step (the module
named by ``kernels.json`` "decode_step") / the ticks the engine counted in
that window.  The tick sets how fast every slot emits: moves
``out_tok_s``.
"""


def read(ctx):
    t, n = ctx["trace"].time_of(ctx["patterns"]["modules"]["decode_step"],
                                modules=True)
    ticks = ctx["ticks"][1] - ctx["ticks"][0]
    return 1e3 * t / ticks if n and ticks else None
