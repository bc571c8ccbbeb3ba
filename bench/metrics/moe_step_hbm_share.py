"""HBM share of the decode steps of a MoE model (model step layer).

Bytes each decode step needs (the configuration's model module's
``step_bytes``: the packed attention linears, the router, the packed
experts the tick's tokens reach, the norms, the LM head, and the bf16 K and
V each active slot's query sees), summed over the traced window's ticks /
the window's seconds / the chip's HBM bandwidth.  Moves ``out_tok_s``.
Reads nothing for a model module without ``step_bytes``.
"""

from lib import spec


def read(ctx):
    count = getattr(spec.model_module(ctx["cfg"]), "step_bytes", None)
    if count is None:
        return None
    cfg, pk, loop, tr = ctx["cfg"], ctx["peaks"], ctx["loop"], ctx["trace"]
    total = sum(count(cfg, loop.active_positions(tick))
                for tick in range(*ctx["ticks"]))
    w = tr.window_s()
    return 100.0 * total / w / pk["hbm_bytes_per_s"] if total and w > 0 \
        else None
