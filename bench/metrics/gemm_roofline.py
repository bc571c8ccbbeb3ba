"""Roofline share of the fused DAS ternary GEMM (kernels/das_gemm.py).

The least time the chip needs for every fused GEMM call of the traced
window (``costs.ternary_gemm`` per call: the decode step's seven linears
per layer at M = the slot count, and each admitted prompt's packed prefill
at M = one pack per call) / the device time of the calls the trace names
``das_ternary_gemm``.  Bandwidth-bound at every served shape.  Nothing is
read where no such call ran (a configuration whose linears cannot take the
fused path).  Moves ``out_tok_s``.
"""


def read(ctx):
    costs = ctx["costs"]
    t, n = ctx["trace"].time_of(ctx["patterns"]["ops"]["das_ternary_gemm"])
    if not n or t <= 0:
        return None
    cfg, pk, loop = ctx["cfg"], ctx["peaks"], ctx["loop"]
    das, pack = cfg["das"], cfg["lpsa"]["pack"]
    layers = cfg["num_hidden_layers"]
    ticks = ctx["ticks"][1] - ctx["ticks"][0]
    packs = sum(loop.log[u]["prompt_len"] // pack for u in ctx["uids"])
    roof = 0.0
    for k, nn in costs.linear_shapes(cfg):
        for m, calls in ((ctx["slots"], ticks), (pack, packs)):
            w = costs.ternary_gemm(m, k, nn, das["keep"], das["block"])
            roof += calls * layers * costs.roofline_s(w, pk)[0]
    return 100.0 * roof / t
