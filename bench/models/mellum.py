"""Mellum 2 (JetBrains Mellum2-12B-A2.5B) served ternary, with TENET's DAS:
the program's configuration, the weights drawn from the seed, the plain
reference and the operations a token needs.

A configuration file names this module with ``"model": "mellum"``.  It
provides what ``bitnet.py`` provides (``program_config``, ``bind``,
``logits``, ``first_layer_kv``, ``first_layer_error``) and ``token_flops``
for ``metrics/moe_step_mfu.py``.

The reference imports nothing of the program and takes none of its arrays.
Per block (pre-norm, residual), with T the DAS selection and Q the
per-token int8 fake quant (scale = max|h| / 127 + 1e-6, values rounded and
clipped to +-127), W = trits * scale:
  a = rmsnorm(x) * (1 + g1)
  q, k, v = T(a) W_q, T(a) W_k, T(a) W_v     (32 q heads over 4 kv heads)
  q, k = rope(q), rope(k)
  o = softmax(q k^T / sqrt(d), mask) v
  x = x + T(o) W_o
  a = rmsnorm(x) * (1 + g2)
  p = softmax(a R) (R the router, f32); the 8 largest p, renormalised to
      sum 1, are the gates of their experts, every other expert's gate is 0
  x = x + sum_e gate_e * Q(T(silu(u W_gate,e) * u W_in,e)) W_out,e,
      u = Q(T(a))                    (dropless: every routed token counts)
logits = (rmsnorm(x) * (1 + g_f)) H.  The layers follow ``layer_types``:
a sliding layer's query at q sees keys k <= q with q - k < sliding_window
and rotates at theta^(-i / (d/2)); a full layer's sees every k <= q and
rotates at YaRN's frequencies (HF ``_compute_yarn_parameters`` with
truncation) with cos and sin times ``attention_factor``.

Layer 0 is a sliding layer whose K and V depend on its token and position
alone and are computed exactly as ``bitnet.py``'s (the same weight draws,
rope at the sliding layers' theta), so ``first_layer_kv`` and
``first_layer_error`` are bitnet.py's.

Precision: as bitnet.py (the configuration's dtype between operations, f32
inside them at ``highest``; ``act_dtype`` rounds the activation side of
every matmul: the control).  An expert's weights are its trits times its
scale rounded to the configuration's dtype.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import math
import os

import numpy as np


def _bitnet():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "bitnet.py")
    sp = importlib.util.spec_from_file_location("bench_model_bitnet_base",
                                                path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod


B = _bitnet()

# attention linears (bitnet.py's draws), then the MoE tensors
LINEARS = ("wq", "wk", "wv", "wo")
EXPERTS = {"experts_gate": "w_gate", "experts_in": "w_in",
           "experts_out": "w_out"}
DENSE = ("embed", "head", "final_norm", "norm1", "norm2")
_IDS = {"router": 12, "experts_gate": 13, "experts_in": 14, "experts_out": 15}
KINDS = {"local": "sliding_attention", "attn": "full_attention"}

# configuration-file key -> attribute of the program's ModelConfig
_SIZE_KEYS = {
    "hidden_size": "d_model",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim_",
    "vocab_size": "vocab",
    "tie_word_embeddings": "tie_embeddings",
    "sliding_window": "window",
    "hidden_act": "act",
}


# -- the program's configuration -------------------------------------------

def program_config(cfg_file: dict):
    """The program's ModelConfig for a configuration file, after checking
    that every size and mechanism the file states is what the program runs:
    widths, layer kinds, window, experts, router, RoPE of each kind, pack,
    DAS and the serving format."""
    from repro.configs import get_config
    name = cfg_file["name"]
    try:
        cfg = get_config(cfg_file["program_config"])
    except KeyError:
        raise SystemExit(f"bench: {name}: the program has no configuration "
                         f"{cfg_file['program_config']!r}") from None
    got = {k: getattr(cfg, a) for k, a in _SIZE_KEYS.items()}
    e, yarn = cfg.moe, cfg.rope_yarn
    got.update(
        layer_types=[KINDS.get(k) for k in cfg.layer_kinds()],
        mlp_layer_types=["sparse" if e else "dense"] * cfg.n_layers,
        num_experts=e and e.n_experts,
        num_experts_per_tok=e and e.top_k,
        moe_intermediate_size=e and e.d_expert,
        lpsa={"pack": cfg.lpsa.chunk},
        das={"block": cfg.ternary.das.block, "keep": cfg.ternary.das.keep},
        rope_parameters={
            "full_attention": yarn and {
                "rope_type": "yarn", "rope_theta": cfg.rope_theta,
                "factor": yarn.factor,
                "original_max_position_embeddings": yarn.original_max,
                "beta_fast": yarn.beta_fast, "beta_slow": yarn.beta_slow,
                "attention_factor": yarn.attention_factor},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": cfg.rope_theta}})
    for k, v in got.items():
        if cfg_file[k] != v:
            raise SystemExit(f"bench: {name}: the program runs {k}={v!r}, "
                             f"the configuration file says {cfg_file[k]!r}")
    # full layers stay full at serve time; no shared expert; the router's
    # gates renormalised; no attention bias; rmsnorm at the program's 1e-6
    if not (not cfg.lpsa_on_globals and e.n_shared == 0
            and cfg_file["norm_topk_prob"] and not cfg_file["attention_bias"]
            and cfg_file["rms_norm_eps"] == 1e-6 and cfg.window
            == cfg_file["sliding_window"] and cfg_file["use_sliding_window"]):
        raise SystemExit(f"bench: {name}: the program's attention or router "
                         f"is not the configuration file's")
    if not (cfg.dtype == cfg_file["dtype"]
            and cfg.ternary.serve_format == "packed"):
        raise SystemExit(f"bench: {name}: the program's serving format is "
                         f"not the configuration file's")
    return cfg


# -- weights ---------------------------------------------------------------

def _as_bitnet(cfg: dict) -> dict:
    """The configuration in bitnet.py's terms, for its draws and its first
    layer (a sliding layer: no sink, plain rope at the sliding theta)."""
    return dict(cfg, rope_theta=float(
        cfg["rope_parameters"]["sliding_attention"]["rope_theta"]),
        lpsa={"sink": 0, "window": cfg["sliding_window"],
              "pack": cfg["lpsa"]["pack"]})


def tensor(key, cfg: dict, name: str, layer=None, expert=None):
    """One tensor of the model, as the benchmark draws it.  The attention
    linears, norms, embedding and head are bitnet.py's draws.  Router
    (d, E): integers in +-127 times 2^-11 (std 0.036, router logits of
    std about 1.7 at d 2304).  An expert's linear: bitnet.py's packing
    (uniform trits, scale 0.6-1.0 / sqrt(fan_in)), drawn per layer and
    expert.  ``layer`` and ``expert`` may be traced indices."""
    import jax
    import jax.numpy as jnp
    if name not in _IDS:
        return B.tensor(key, _as_bitnet(cfg), name, layer)
    k = jax.random.fold_in(jax.random.fold_in(key, _IDS[name]), layer)
    if name == "router":
        r = jax.random.randint(k, (cfg["hidden_size"], cfg["num_experts"]),
                               -127, 128, jnp.int32)
        return (r.astype(jnp.float32) * 2.0 ** -11).astype(cfg["dtype"])
    # an expert's linear is drawn as bitnet.py draws the FFN linear of the
    # same shape, from a key of its own layer and expert
    k = jax.random.fold_in(k, expert)
    ffn = dict(_as_bitnet(cfg), intermediate_size=cfg["moe_intermediate_size"])
    return B.tensor(k, ffn, EXPERTS[name], None)


def _name_of(keys) -> str:
    for name in (*EXPERTS, "router", *LINEARS, *DENSE):
        if name in keys:
            return name
    raise SystemExit(f"bench: unknown leaf in the serving weights: {keys}")


def bind(key, cfg: dict, abstract):
    """The program's serving-weight tree, every leaf drawn by `tensor` (see
    bitnet.py's ``bind``).  Stacked leaves carry the layer groups on their
    first axis, layer = group * pattern + position; an expert stack its
    experts on the next one; packed rows past ceil(K/5) are zero trits."""
    import jax
    import jax.numpy as jnp
    period = _period(cfg["layer_types"])
    first_tail = cfg["num_hidden_layers"] - len(abstract["layers"]["tail"])

    def make(key):
        def leaf(path, a):
            keys = [getattr(p, "key", getattr(p, "idx", None)) for p in path]
            name, kind = _name_of(keys), keys[-1]
            if "stacked" in keys:
                j = keys[keys.index("stacked") + 1]
                layers = jnp.arange(a.shape[0]) * period + j
            else:
                layers = None
            layer = (first_tail + keys[keys.index("tail") + 1]
                     if "tail" in keys else None)

            def one(layer):
                if name in EXPERTS:
                    def per(e):
                        byts, scale = tensor(key, cfg, name, layer, e)
                        if kind == "scale":
                            return scale.reshape(1, 1)
                        extra = a.shape[-2] - byts.shape[0]
                        return jnp.pad(byts, ((0, extra), (0, 0)),
                                       constant_values=B.ZERO_BYTE)
                    return jax.vmap(per)(jnp.arange(cfg["num_experts"]))
                t = tensor(key, cfg, name, layer)
                if name not in LINEARS:
                    return t
                if kind == "scale":
                    return t[1]
                extra = a.shape[-2] - t[0].shape[0]
                return jnp.pad(t[0], ((0, extra), (0, 0)),
                               constant_values=B.ZERO_BYTE)
            out = jax.vmap(one)(layers) if layers is not None else one(layer)
            if out.shape != a.shape or out.dtype != a.dtype:
                raise SystemExit(f"bench: drew {name}/{kind} as {out.shape} "
                                 f"{out.dtype}, the program takes {a.shape} "
                                 f"{a.dtype}")
            return out
        return jax.tree_util.tree_map_with_path(leaf, abstract)

    return jax.jit(make)(key)


def _period(types: list) -> int:
    """Length of the repeating pattern of ``layer_types``."""
    for n in range(1, len(types) + 1):
        if all(t == types[i % n] for i, t in enumerate(types)):
            return n
    return len(types)


# -- the reference ---------------------------------------------------------

def yarn_inv_freq(dim: int, theta: float, rp: dict) -> np.ndarray:
    """YaRN inverse frequencies (dim/2,) from HF's ``rope_parameters``:
    correction dims of beta_fast and beta_slow rotations over the original
    context, floored and ceiled; frequency index i keeps theta^(-2i/dim)
    below the first, takes it / factor above the second, ramped linearly
    between."""
    orig = rp["original_max_position_embeddings"]

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (
            2 * math.log(theta))
    low = max(math.floor(corr(rp["beta_fast"])), 0)
    high = min(math.ceil(corr(rp["beta_slow"])), dim - 1)
    high = high + 0.001 if low == high else high
    i = np.arange(dim // 2, dtype=np.float64)
    base = theta ** (-2 * i / dim)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    return base * (1 - ramp) + base / rp["factor"] * ramp


def forward_fn(cfg: dict, act_dtype: str | None = None):
    """jitted reference: (key, tokens (T,)) -> logits (T, V)."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(cfg["dtype"])
    low = jnp.dtype(act_dtype) if act_dtype else None
    h, hd = cfg["num_attention_heads"], cfg["head_dim"]
    rep = h // cfg["num_key_value_heads"]
    block, keep = cfg["das"]["block"], cfg["das"]["keep"]
    eps, vocab = cfg["rms_norm_eps"], cfg["vocab_size"]
    n_exp, top = cfg["num_experts"], cfg["num_experts_per_tok"]
    window = cfg["sliding_window"]
    rope = cfg["rope_parameters"]
    full = np.array([t == "full_attention" for t in cfg["layer_types"]])
    freqs = {k: np.asarray(yarn_inv_freq(hd, float(rope[k]["rope_theta"]),
                                         rope[k])
                           if rope[k]["rope_type"] == "yarn" else
                           float(rope[k]["rope_theta"]) ** (
                               -np.arange(hd // 2) / (hd // 2)), np.float32)
             for k in rope}
    mscale = {k: float(rope[k].get("attention_factor", 1.0)) for k in rope}

    def rnd(x):
        return x.astype(dt).astype(jnp.float32)

    def operand(x):
        x = rnd(x)
        return x if low is None else x.astype(low).astype(jnp.float32)

    def das(x):
        return B._das(rnd(x), block, keep)

    def int8(x):
        amax = jnp.max(jnp.abs(x), -1, keepdims=True)
        s = rnd(rnd(amax / 127.0) + 1e-6)
        return rnd(jnp.clip(jnp.round(x / s), -127, 127) * s)

    def linear(key, name, layer, x):
        byts, scale = tensor(key, cfg, name, layer)
        w = B._unpack(byts, h * hd if name == "wo" else cfg["hidden_size"])
        return rnd(jnp.dot(operand(das(x)), w, precision="highest") * scale)

    def rotate(x, pos, kind):
        f = jnp.asarray(freqs[kind])
        ang = pos.astype(jnp.float32)[:, None] * f
        c = (jnp.cos(ang) * mscale[kind])[:, None, :]
        s = (jnp.sin(ang) * mscale[kind])[:, None, :]
        half = x.shape[-1] // 2
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)

    def attention(key, x, layer, pos, is_full):
        t = x.shape[0]
        a = rnd(B._rmsnorm(x, tensor(key, cfg, "norm1", layer), eps))
        q = linear(key, "wq", layer, a).reshape(t, h, hd)
        k = linear(key, "wk", layer, a).reshape(t, -1, hd)
        v = linear(key, "wv", layer, a).reshape(t, -1, hd)
        q, k = (rnd(jnp.where(is_full, rotate(z, pos, "full_attention"),
                              rotate(z, pos, "sliding_attention")))
                for z in (q, k))
        k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
        s = jnp.einsum("qhd,khd->hqk", operand(q), operand(k),
                       precision="highest") * hd ** -0.5
        qp, kp = pos[:, None], pos[None, :]
        seen = (kp <= qp) & (is_full | (qp - kp < window))
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", p, operand(v), precision="highest")
        return rnd(x + linear(key, "wo", layer, rnd(o).reshape(t, h * hd)))

    def moe(key, x, layer):
        a = rnd(B._rmsnorm(x, tensor(key, cfg, "norm2", layer), eps))
        router = tensor(key, cfg, "router", layer).astype(jnp.float32)
        probs = jax.nn.softmax(jnp.dot(operand(a), router,
                                       precision="highest"), axis=-1)
        gate, idx = jax.lax.top_k(probs, top)
        gate = gate / gate.sum(-1, keepdims=True)
        u = operand(int8(das(a)))
        kd, kf = cfg["hidden_size"], cfg["moe_intermediate_size"]

        def expert(y, e):
            def w(name, k):
                byts, scale = tensor(key, cfg, name, layer, e)
                return B._unpack(byts, k) * rnd(scale)
            g = jnp.dot(u, w("experts_gate", kd), precision="highest")
            i = jnp.dot(u, w("experts_in", kd), precision="highest")
            m = operand(int8(das(rnd(rnd(jax.nn.silu(rnd(g))) * rnd(i)))))
            ye = rnd(jnp.dot(m, w("experts_out", kf), precision="highest"))
            ge = rnd(jnp.sum(jnp.where(idx == e, gate, 0.0), -1))
            return y + ye * ge[:, None], None
        y, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(n_exp))
        return rnd(x + rnd(y))

    def head(key):
        if cfg["tie_word_embeddings"]:
            return tensor(key, cfg, "embed")[:vocab].T
        return tensor(key, cfg, "head")[:, :vocab]

    @jax.jit
    def forward(key, tokens):
        pos = jnp.arange(tokens.shape[0])
        x = tensor(key, cfg, "embed")[tokens].astype(jnp.float32)

        def body(x, layer):
            x = attention(key, x, layer, pos, jnp.asarray(full)[layer])
            return moe(key, x, layer), None
        x, _ = jax.lax.scan(body, x, jnp.arange(cfg["num_hidden_layers"]))
        f = operand(B._rmsnorm(x, tensor(key, cfg, "final_norm"), eps))
        return jnp.dot(f, head(key).astype(jnp.float32), precision="highest")

    return forward


@functools.lru_cache(maxsize=4)
def _cached(cfg_json: str, act_dtype: str | None):
    return forward_fn(json.loads(cfg_json), act_dtype)


def logits(key, cfg: dict, seq: np.ndarray, pad_to: int,
           act_dtype: str | None = None) -> np.ndarray:
    """Reference logits (len(seq), V) for one sequence, padded to `pad_to`
    positions (causal: the pad changes nothing before it)."""
    import jax
    toks = np.zeros(pad_to, np.int32)
    toks[:len(seq)] = seq
    with jax.default_matmul_precision("highest"):
        out = _cached(json.dumps(cfg, sort_keys=True), act_dtype)(key, toks)
    return np.asarray(jax.device_get(out))[:len(seq)]


def first_layer_kv(key, cfg: dict, tokens, pos, act_dtype=None):
    """Reference K and V of layer 0 (a sliding layer): bitnet.py's."""
    return B.first_layer_kv(key, _as_bitnet(cfg), tokens, pos, act_dtype)


def first_layer_error(key, cfg: dict, tokens, pos, k, v,
                      ulps: int = B.TIE_ULPS):
    """How far given layer-0 K and V lie from the reference's, after the
    DAS near-tie directions are projected out: bitnet.py's."""
    return B.first_layer_error(key, _as_bitnet(cfg), tokens, pos, k, v, ulps)


# -- operations a token needs (metrics/moe_step_mfu.py) ----------------------

def live_keys(cfg: dict, pos: int, full: bool) -> int:
    """Keys a query at `pos` sees: pos + 1 on a full layer, at most the
    window on a sliding one."""
    return pos + 1 if full else min(pos + 1, cfg["sliding_window"])


def layer_flops(cfg: dict, pos: int, full: bool) -> float:
    """Operations one token at `pos` needs in one layer: the DAS-kept lanes
    of q, k, v and o; the f32 router; `num_experts_per_tok` SwiGLU experts
    at their DAS-kept lanes; attention over min(pos + 1, window) keys on a
    sliding layer and pos + 1 on a full one (QK^T and PV)."""
    das = cfg["das"]

    def lin(k, n):
        return 2 * (k // das["block"] * das["keep"] + k % das["block"]) * n
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    proj = lin(d, q) + 2 * lin(d, kv) + lin(q, d)
    router = 2 * d * cfg["num_experts"]
    experts = cfg["num_experts_per_tok"] * (2 * lin(d, f) + lin(f, d))
    return proj + router + experts + 4 * q * live_keys(cfg, pos, full)


def token_flops(cfg: dict, pos: int) -> float:
    """Operations one token at `pos` needs: every layer, then the LM head."""
    return (sum(layer_flops(cfg, pos, t == "full_attention")
                for t in cfg["layer_types"])
            + 2 * cfg["hidden_size"] * cfg["vocab_size"])




# -- work and bytes of a decode tick (metrics/moe_attn_roofline.py and
# -- metrics/moe_step_hbm_share.py) ------------------------------------------

BF16 = 2


def attention_work(cfg: dict, positions) -> dict:
    """Decode attention of one tick, one query at each of `positions`, over
    every layer: QK^T and PV over the keys each query sees (`live_keys`);
    bf16 K and V of those keys read once (the kv heads', GQA), bf16 q in
    and out."""
    h, kvh, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    flops = nbytes = 0
    for t in cfg["layer_types"]:
        live = sum(live_keys(cfg, p, t == "full_attention") for p in positions)
        flops += 4 * h * hd * live
        nbytes += 2 * BF16 * kvh * hd * live + 2 * BF16 * len(positions) * h * hd
    return {"flops": flops, "bytes": nbytes}


def experts_reached(cfg: dict, tokens: int) -> float:
    """Experts one layer's `tokens` tokens reach, expected when each token
    picks its top k distinct experts of E uniformly: E (1 - (1 - k/E)^n)."""
    e, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    return e * (1 - (1 - k / e) ** tokens)


def step_bytes(cfg: dict, positions) -> float:
    """Bytes a decode tick of one token at each of `positions` needs: per
    layer the packed q, k, v and o (K*N/5 and an f32 scale each), the bf16
    router, the packed SwiGLU of the experts its tokens reach
    (`experts_reached`) and two bf16 norms; the bf16 LM head and final norm
    once; bf16 K and V of every key a query sees."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]

    def packed(k, n):
        return k * n / 5 + 4
    attn = packed(d, q) + 2 * packed(d, kv) + packed(q, d)
    expert = 2 * packed(d, f) + packed(f, d)
    layer = (attn + BF16 * d * cfg["num_experts"] + 2 * BF16 * d
             + experts_reached(cfg, len(positions)) * expert)
    kv_read = sum(2 * BF16 * kv * live_keys(cfg, p, t == "full_attention")
                  for t in cfg["layer_types"] for p in positions)
    return (cfg["num_hidden_layers"] * layer + kv_read
            + BF16 * cfg["vocab_size"] * d + BF16 * d)
