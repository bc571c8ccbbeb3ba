"""The work a kernel or a model step needs, computed from shapes.

The yardstick is tied to the work and not to the code that does it:
weights count at the TWD packing (1.6 bits a weight), activations and
outputs in bf16, attention over the keys LPSA lets a query see.  A change
that reads more bytes than these reads further from its roofline.

Bounds: at the shapes served here every call is bandwidth-bound; the
roofline time takes the larger of the two bounds anyway and ``bound`` says
which one it was.
"""

from __future__ import annotations

import json
import os

BF16 = 2


def peaks(kind: str) -> dict:
    """Peaks of one chip of JAX's `device_kind`; an unknown kind is an
    error, never a default."""
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    if kind not in table["kinds"]:
        raise SystemExit(f"bench: device kind {kind!r} is not in the peaks "
                         f"table ({', '.join(table['kinds'])})")
    return table["kinds"][kind]


def das_k(k: int, keep: int, block: int) -> int:
    """Input lanes a DAS linear multiplies: `keep` of every whole block of
    `block`, and every lane past the last whole block."""
    return k // block * keep + k % block


def ternary_gemm(m: int, k: int, n: int, keep: int, block: int) -> dict:
    """One DAS ternary GEMM (M, K) x (K, N): operations on the kept lanes;
    bytes of packed weights (K*N/5), the f32 scale, bf16 activations in
    and bf16 outputs."""
    return {"flops": 2 * m * das_k(k, keep, block) * n,
            "bytes": k * n / 5 + 4 + BF16 * m * k + BF16 * m * n}


def live_keys(pos: int, sink: int, window: int) -> int:
    """Keys a query at `pos` sees under LPSA."""
    return min(pos + 1, sink + window)


def lpsa_attention(live: int, heads: int, kv_heads: int, head_dim: int,
                   queries: int = 1) -> dict:
    """LPSA attention of `queries` queries over `live` keys (summed over
    queries when they differ): QK^T and PV, bf16 K and V read once, bf16 q
    in and out."""
    return {"flops": 4 * heads * head_dim * live,
            "bytes": 2 * BF16 * kv_heads * head_dim * live
            + 2 * BF16 * queries * heads * head_dim}


def roofline_s(work: dict, pk: dict) -> tuple[float, str]:
    c = work["flops"] / pk["bf16_flops"]
    b = work["bytes"] / pk["hbm_bytes_per_s"]
    return (c, "compute") if c >= b else (b, "bandwidth")


def linear_shapes(cfg: dict) -> list[tuple[int, int]]:
    """(K, N) of every ternary linear of one block."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return [(d, q), (d, kv), (d, kv), (q, d), (d, f), (d, f), (f, d)]


def weight_bytes(cfg: dict) -> float:
    """Serving weights one step reads: packed linears with their scales,
    the norms and the bf16 LM head (the embedding where the two are tied;
    a step looks up only its tokens' rows of an untied embedding)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    per = sum(k * n / 5 + 4 for k, n in linear_shapes(cfg)) + 2 * BF16 * d
    return cfg["num_hidden_layers"] * per + BF16 * v * d + BF16 * d


def kv_bytes(live: int, cfg: dict) -> float:
    """bf16 K and V of `live` positions across every layer."""
    return (2 * BF16 * cfg["num_key_value_heads"] * cfg["head_dim"] * live
            * cfg["num_hidden_layers"])


def token_flops(cfg: dict, pos: int) -> float:
    """Operations the model needs for one token at position `pos`: the DAS
    linears, LPSA attention and the LM head."""
    das = cfg["das"]
    lin = sum(2 * das_k(k, das["keep"], das["block"]) * n
              for k, n in linear_shapes(cfg))
    live = live_keys(pos, cfg["lpsa"]["sink"], cfg["lpsa"]["window"])
    att = 4 * cfg["num_attention_heads"] * cfg["head_dim"] * live
    return (cfg["num_hidden_layers"] * (lin + att)
            + 2 * cfg["hidden_size"] * cfg["vocab_size"])
