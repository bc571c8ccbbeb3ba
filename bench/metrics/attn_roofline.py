"""Roofline share of the LPSA decode attention (kernels/sparse_attn.py).

The least time the chip needs for the traced window's decode attention
(``costs.lpsa_attention`` per layer and tick, over the keys each active
slot's query sees: min(position + 1, sink + window), not the ring's
capacity) / the device time of the calls the trace names
``sparse_attention``.  Bandwidth-bound.  Moves ``out_tok_s``.
"""


def read(ctx):
    costs = ctx["costs"]
    t, n = ctx["trace"].time_of(ctx["patterns"]["ops"]["sparse_attention"])
    if not n or t <= 0:
        return None
    cfg, pk, loop = ctx["cfg"], ctx["peaks"], ctx["loop"]
    sink, win = cfg["lpsa"]["sink"], cfg["lpsa"]["window"]
    roof = 0.0
    for tick in range(*ctx["ticks"]):
        pos = loop.active_positions(tick)
        live = sum(costs.live_keys(p, sink, win) for p in pos)
        w = costs.lpsa_attention(live, cfg["num_attention_heads"],
                                 cfg["num_key_value_heads"],
                                 cfg["head_dim"], queries=len(pos))
        roof += cfg["num_hidden_layers"] * costs.roofline_s(w, pk)[0]
    return 100.0 * roof / t
