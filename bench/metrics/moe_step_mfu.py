"""Model FLOP utilisation of the whole serving loop of a MoE model (model
step layer).

Operations the model needs for every token it processed in the traced
window (the configuration's model module's ``token_flops``: per layer the
DAS-kept lanes of q, k, v and o, the router, the routed experts' SwiGLU at
their DAS-kept lanes and attention over the keys the layer's kind lets a
query see; the LM head once; each active slot's decode-step token at its
position, and every token of each admitted prompt's packed prefill) / the
window's seconds / the chip's bf16 peak.  Moves ``out_tok_s``.  Reads
nothing for a model module without ``token_flops``.
"""

from lib import spec


def read(ctx):
    cfg, pk, loop, tr = ctx["cfg"], ctx["peaks"], ctx["loop"], ctx["trace"]
    count = getattr(spec.model_module(cfg), "token_flops", None)
    if count is None:
        return None
    pack = cfg["lpsa"]["pack"]
    flops = 0.0
    for tick in range(*ctx["ticks"]):
        flops += sum(count(cfg, p) for p in loop.active_positions(tick))
    for u in ctx["uids"]:
        prefix = loop.log[u]["prompt_len"] // pack * pack
        flops += sum(count(cfg, p) for p in range(prefix))
    w = tr.window_s()
    return 100.0 * flops / w / pk["bf16_flops"] if flops and w > 0 else None
