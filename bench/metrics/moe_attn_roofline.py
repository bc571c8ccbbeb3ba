"""Roofline share of the decode attention kernel (kernels/sparse_attn.py)
on a model whose layers differ in the keys they keep (kernels layer).

The least time the chip needs for the traced window's decode attention
(the configuration's model module's ``attention_work`` per tick: one query
per active slot in every layer, over the keys the layer's kind lets it
see, GQA's bf16 K and V read once) / the device time of the calls the
trace names ``sparse_attention``.  Bandwidth-bound.  Moves ``out_tok_s``.
Reads nothing for a model module without ``attention_work``.
"""

from lib import spec


def read(ctx):
    work = getattr(spec.model_module(ctx["cfg"]), "attention_work", None)
    if work is None:
        return None
    t, n = ctx["trace"].time_of(ctx["patterns"]["ops"]["sparse_attention"])
    if not n or t <= 0:
        return None
    costs, cfg, pk, loop = ctx["costs"], ctx["cfg"], ctx["peaks"], ctx["loop"]
    roof = sum(costs.roofline_s(work(cfg, loop.active_positions(tick)),
                                pk)[0]
               for tick in range(*ctx["ticks"]))
    return 100.0 * roof / t
