"""Share of the traced window in which no operation ran on the device
(device layer): 1 - union of the operation intervals / the window.
Moves ``out_tok_s``.
"""


def read(ctx):
    tr = ctx["trace"]
    w = tr.window_s()
    return 100.0 * (1.0 - tr.busy_s() / w) if w > 0 else None
