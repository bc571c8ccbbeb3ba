"""HBM share of the decode steps (model step layer).

Bytes each decode step needs (the serving weights, ``costs.weight_bytes``,
plus the bf16 K and V each active slot's query sees across all layers,
``costs.kv_bytes``), summed over the traced window's ticks / the window's
seconds / the chip's HBM bandwidth.  Moves ``out_tok_s``.
"""


def read(ctx):
    costs = ctx["costs"]
    cfg, pk, loop, tr = ctx["cfg"], ctx["peaks"], ctx["loop"], ctx["trace"]
    sink, win = cfg["lpsa"]["sink"], cfg["lpsa"]["window"]
    wb = costs.weight_bytes(cfg)
    total = 0.0
    for tick in range(*ctx["ticks"]):
        total += wb + sum(costs.kv_bytes(costs.live_keys(p, sink, win), cfg)
                          for p in loop.active_positions(tick))
    w = tr.window_s()
    return 100.0 * total / w / pk["hbm_bytes_per_s"] if total and w > 0 \
        else None
