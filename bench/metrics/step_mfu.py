"""Model FLOP utilisation of the whole serving loop (model step layer).

Operations the model needs for every token it processed in the traced
window (``costs.token_flops``: each active slot's decode-step token at its
position, and every token of each admitted prompt's packed prefill) / the
window's seconds / the chip's bf16 peak.  Bounds every kernel's share from
above.  Moves ``out_tok_s``.
"""


def read(ctx):
    costs = ctx["costs"]
    cfg, pk, loop, tr = ctx["cfg"], ctx["peaks"], ctx["loop"], ctx["trace"]
    pack = cfg["lpsa"]["pack"]
    flops = 0.0
    for tick in range(*ctx["ticks"]):
        flops += sum(costs.token_flops(cfg, p)
                     for p in loop.active_positions(tick))
    for u in ctx["uids"]:
        prefix = loop.log[u]["prompt_len"] // pack * pack
        flops += sum(costs.token_flops(cfg, p) for p in range(prefix))
    w = tr.window_s()
    return 100.0 * flops / w / pk["bf16_flops"] if flops and w > 0 else None
