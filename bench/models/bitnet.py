"""BitNet b1.58 with TENET's DAS and LPSA: the program's configuration, the
weights drawn from the seed, and the plain reference.

A configuration file names this module with ``"model": "bitnet"``; the
harness loads it by that name (``spec.model_module``), so another family
adds its own module and edits none.  It provides:

  program_config(cfg)   the program's ModelConfig, checked against the file
  bind(key, cfg, tree)  the program's serving weights, drawn on the device
  logits(...)           reference logits of one sequence
  first_layer_kv(...)   reference K and V of the first layer
  first_layer_error(...) how far given first-layer K and V lie from them

The reference imports nothing of the program and takes none of its arrays:
it draws the weights again from the seed (``tensor``) and decodes the TWD
bytes itself.  The model, per block (pre-norm, residual), with T the DAS
selection (the `keep` largest |h| of every `block` lanes, ties to the lower
lane, lanes past the last whole block dense) and W = trits * scale:
  a = rmsnorm(x) * (1 + g1)
  q, k, v = T(a) W_q, T(a) W_k, T(a) W_v    (k, v shared by groups of heads)
  q, k = rope(q), rope(k)
  o = softmax(q k^T / sqrt(d), LPSA mask) v
  x = x + T(o) W_o
  a = rmsnorm(x) * (1 + g2)
  x = x + T(silu(T(a) W_gate) * T(a) W_in) W_out
logits = (rmsnorm(x) * (1 + g_f)) H over the first V columns, H the LM head
(the embedding's transpose where the file ties them).  LPSA: position q
sees key k iff k <= q and (k < sink or q - k < window).  RoPE rotates the
two halves of each head at frequencies theta^(-i / (d/2)).

Precision.  The configuration states bf16: every tensor that passes from
one operation to the next is rounded to bf16; the arithmetic inside an
operation is f32 (matmuls at ``highest``).  ``act_dtype`` is the control:
the same pass with every matmul operand on the activation side rounded to
that type (fp8 e4m3, the nearest precision below bf16).
"""

from __future__ import annotations

import dataclasses
import functools
import json

import numpy as np

# the input width (fan-in) of each linear, by its name in a block
LINEARS = {"wq": "d_model", "wk": "d_model", "wv": "d_model", "wo": "q_dim",
           "w_gate": "d_model", "w_in": "d_model", "w_out": "d_ff"}
DENSE = ("embed", "head", "final_norm", "norm1", "norm2")
_IDS = {name: i for i, name in enumerate(
    ["embed", "final_norm", "norm1", "norm2", *LINEARS, "head"])}
ZERO_BYTE = 121          # 1 + 3 + 9 + 27 + 81: five zero trits

# configuration-file key -> attribute of the program's ModelConfig
_SIZE_KEYS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim_",
    "vocab_size": "vocab",
    "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
}


# -- the program's configuration -------------------------------------------

def program_config(cfg_file: dict):
    """The program's ModelConfig for a configuration file: its registered
    config with the file's vocabulary and head tying, after checking that
    every size the file states is the size the program runs."""
    from repro.configs import get_config
    cfg = dataclasses.replace(
        get_config(cfg_file["program_config"]),
        vocab=cfg_file["vocab_size"],
        tie_embeddings=cfg_file["tie_word_embeddings"])
    got = {k: getattr(cfg, a) for k, a in _SIZE_KEYS.items()}
    got["lpsa"] = {"sink": cfg.lpsa.sink, "window": cfg.lpsa.window,
                   "pack": cfg.lpsa.chunk}
    got["das"] = {"block": cfg.ternary.das.block, "keep": cfg.ternary.das.keep}
    for k, v in got.items():
        if cfg_file[k] != v:
            raise SystemExit(f"bench: {cfg_file['name']}: the program runs "
                             f"{k}={v!r}, the configuration file says "
                             f"{cfg_file[k]!r}")
    if not (cfg.dtype == cfg_file["dtype"]
            and cfg.ternary.serve_format == "packed"):
        raise SystemExit(f"bench: {cfg_file['name']}: the program's serving "
                         f"format is not the configuration file's")
    return cfg


# -- weights ---------------------------------------------------------------

def dims(cfg: dict) -> dict:
    return {"d_model": cfg["hidden_size"], "d_ff": cfg["intermediate_size"],
            "q_dim": cfg["num_attention_heads"] * cfg["head_dim"],
            "kv_dim": cfg["num_key_value_heads"] * cfg["head_dim"]}


def tensor(key, cfg: dict, name: str, layer: int | None):
    """One tensor of the model, as the benchmark draws it.

    Linears: (bytes (ceil(K/5), N) uint8, scale () f32), uniform trits, the
    trits past K in the last byte zero (TWD: five trits a byte, base 3,
    least significant first, along the input axis).  Norm scales: (d,),
    integers in +-63 times 2^-9.  Embedding (V padded to 128 rows, d) and
    head (d, V padded): integers in +-127 times 2^-12 (std 0.018); rows or
    columns past V are never read and their logits are masked.  Integers
    times a power of two are exact in bf16, so every compiled context that
    draws them gets the same bits.  ``layer`` may be a traced index."""
    import jax
    import jax.numpy as jnp
    k = jax.random.fold_in(key, _IDS[name])
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    d, dt = cfg["hidden_size"], jnp.dtype(cfg["dtype"])
    rows = -(-cfg["vocab_size"] // 128) * 128
    if name in ("embed", "head"):
        shape = (rows, d) if name == "embed" else (d, rows)
        e = jax.random.randint(k, shape, -127, 128, jnp.int32)
        return (e.astype(jnp.float32) * 2.0 ** -12).astype(dt)
    if name in ("final_norm", "norm1", "norm2"):
        g = jax.random.randint(k, (d,), -63, 64, jnp.int32)
        return (g.astype(jnp.float32) * 2.0 ** -9).astype(dt)
    dd = dims(cfg)
    fan_in = dd[LINEARS[name]]
    fan_out = {"wq": dd["q_dim"], "wk": dd["kv_dim"], "wv": dd["kv_dim"],
               "w_gate": dd["d_ff"], "w_in": dd["d_ff"]}.get(name, d)
    rows = -(-fan_in // 5)
    kb, ks = jax.random.split(k)
    # row r holds trits 5r .. 5r+4; those at or past K are zero (digit 1)
    valid = np.clip(fan_in - 5 * np.arange(rows), 0, 5)
    span = (3 ** valid)[:, None]
    pad = ((243 - 3 ** valid) // 2)[:, None]
    low = jax.random.randint(kb, (rows, fan_out), 0, 2 ** 30, jnp.int32)
    byts = (low % jnp.asarray(span, jnp.int32)
            + jnp.asarray(pad, jnp.int32)).astype(jnp.uint8)
    scale = jax.random.uniform(ks, (), jnp.float32, 0.6, 1.0) * fan_in ** -0.5
    return byts, scale


def _name_of(path) -> tuple[str, str]:
    """(tensor name, leaf kind) for a leaf path of the program's tree."""
    keys = [getattr(p, "key", getattr(p, "idx", None)) for p in path]
    for name in (*LINEARS, *DENSE):
        if name in keys:
            return name, keys[-1]
    raise SystemExit(f"bench: unknown leaf in the serving weights: {keys}")


def bind(key, cfg: dict, abstract):
    """The program's serving-weight tree, every leaf drawn by `tensor`.

    `abstract` is the tree of shapes and dtypes the program's serving path
    takes (from ``jax.eval_shape`` of its export).  Stacked leaves carry the
    layers on their first axis; packed rows past ceil(K/5) are zero trits.
    One jitted call makes every leaf on the device; the key is its argument,
    so every seed runs the one compiled program."""
    import jax
    import jax.numpy as jnp

    def make(key):
        def leaf(path, a):
            name, kind = _name_of(path)
            keys = [getattr(p, "key", getattr(p, "idx", None)) for p in path]
            if name in LINEARS:
                stacked = a.ndim == (3 if kind == "packed" else 1)
            else:
                stacked = name in ("norm1", "norm2") and a.ndim == 2
            # an unrolled layer (the program's "tail") carries its index
            fixed = keys[keys.index("tail") + 1] if "tail" in keys else None
            layers = jnp.arange(a.shape[0]) if stacked else None

            def one(layer):
                t = tensor(key, cfg, name, layer)
                if name not in LINEARS:
                    return t
                if kind == "scale":
                    return t[1]
                extra = a.shape[-2] - t[0].shape[0]
                return jnp.pad(t[0], ((0, extra), (0, 0)),
                               constant_values=ZERO_BYTE)
            out = jax.vmap(one)(layers) if stacked else one(fixed)
            if out.shape != a.shape or out.dtype != a.dtype:
                raise SystemExit(f"bench: drew {name}/{kind} as {out.shape} "
                                 f"{out.dtype}, the program takes {a.shape} "
                                 f"{a.dtype}")
            return out
        return jax.tree_util.tree_map_with_path(leaf, abstract)

    return jax.jit(make)(key)


# -- the reference ---------------------------------------------------------

def _unpack(byts, k: int):
    """TWD bytes (rows, N) -> trits (k, N) f32: digit i of byte r is the
    trit at input lane 5r + i, value digit - 1."""
    import jax.numpy as jnp
    b = byts.astype(jnp.int32)
    digits = [(b // 3 ** i) % 3 for i in range(5)]
    t = jnp.stack(digits, axis=1).reshape(-1, byts.shape[-1])[:k]
    return (t - 1).astype(jnp.float32)


def _blocks(x, block: int):
    main = x.shape[-1] - x.shape[-1] % block
    return x[..., :main].reshape(x.shape[:-1] + (main // block, block)), main


def _das(x, block: int, keep: int):
    """Zero all but the `keep` largest |x| of every `block` lanes, ties to
    the lower lane (a stable sort); lanes past the last whole block stay."""
    import jax.numpy as jnp
    xb, main = _blocks(x, block)
    idx = jnp.argsort(-jnp.abs(xb), axis=-1, stable=True)[..., :keep]
    kept = (idx[..., :, None] == jnp.arange(block)).any(-2)
    out = jnp.where(kept, xb, 0).reshape(x.shape[:-1] + (main,))
    return jnp.concatenate([out, x[..., main:]], axis=-1)


def _near_ties(a, block: int, keep: int, ulps: int, mantissa: int):
    """Lanes (..., K) bool whose side of DAS's cut could change if every
    |a| moved by up to `ulps` ulps (of a float with `mantissa` explicit
    bits): in a block whose keep-th and (keep+1)-th largest |a| lie within
    2 * ulps ulps of each other, every lane between them, widened by that
    much (ulps 0: exact ties only)."""
    import jax.numpy as jnp
    ab, main = _blocks(jnp.abs(a), block)
    s = -jnp.sort(-ab, axis=-1)
    hi, lo = s[..., keep - 1:keep], s[..., keep:keep + 1]
    _, e = jnp.frexp(hi)
    delta = 2 * ulps * jnp.ldexp(jnp.ones_like(hi), e - 1 - mantissa)
    near = (hi - lo <= delta) & (ab >= hi - delta) & (ab <= lo + delta)
    near = near.reshape(a.shape[:-1] + (main,))
    return jnp.concatenate(
        [near, jnp.zeros(a.shape[:-1] + (a.shape[-1] - main,), bool)], -1)


def _rmsnorm(x, g, eps: float):
    import jax
    import jax.numpy as jnp
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return y * (1.0 + g.astype(jnp.float32))


def _rope(x, pos, theta: float):
    import jax.numpy as jnp
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freq
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x = x.astype(jnp.float32)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


# first-layer rows compared per chunk, and the most near-tie lanes per row
# whose directions are projected out (see first_layer_error)
CHUNK, MAX_FREE = 64, 128
# how far, in ulps of the configuration's dtype, the program's own rounding
# may move a normed activation before DAS selects on it: on a TPU v5e the
# program's selection differs from one on the stored bf16 values in about
# one block in a hundred, always between lanes within one ulp of the cut
# (0 leaves those swaps in, 1 and 2 clear them alike)
TIE_ULPS = 1


def forward_fn(cfg: dict, act_dtype: str | None = None):
    """jitted functions of the reference: (key, tokens (T,)) -> logits
    (T, V); (key, tokens (P,), positions (P,)) -> the first layer's K and V
    (P, kv heads, head dim); and (key, tokens, positions, K, V, ulps) ->
    per position the relative error of given K and V after the near-tie
    lanes' directions are projected out (see ``first_layer_error``)."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(cfg["dtype"])
    low = jnp.dtype(act_dtype) if act_dtype else None
    dd = dims(cfg)
    h, hd = cfg["num_attention_heads"], cfg["head_dim"]
    rep = h // cfg["num_key_value_heads"]
    sink, window = cfg["lpsa"]["sink"], cfg["lpsa"]["window"]
    block, keep = cfg["das"]["block"], cfg["das"]["keep"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    n_layers, vocab = cfg["num_hidden_layers"], cfg["vocab_size"]

    def rnd(x):                       # the configuration's storage dtype
        return x.astype(dt).astype(jnp.float32)

    def operand(x):                   # activation side of a matmul
        x = rnd(x)
        return x if low is None else x.astype(low).astype(jnp.float32)

    def weight(key, name, layer):
        byts, scale = tensor(key, cfg, name, layer)
        return _unpack(byts, dd[LINEARS[name]]), scale

    def linear(key, name, layer, x):
        w, scale = weight(key, name, layer)
        y = jnp.dot(operand(_das(rnd(x), block, keep)), w,
                    precision="highest")
        return rnd(y * scale)

    def norm1(key, x):
        return rnd(_rmsnorm(x, tensor(key, cfg, "norm1", 0), eps))

    def kv(key, x, layer, pos, a):
        t = x.shape[0]
        k, v = (linear(key, n, layer, a).reshape(t, -1, hd)
                for n in ("wk", "wv"))
        return rnd(_rope(k, pos, theta)), v

    def layer_fn(key, x, layer, pos):
        t = x.shape[0]
        a = rnd(_rmsnorm(x, tensor(key, cfg, "norm1", layer), eps))
        q = rnd(_rope(linear(key, "wq", layer, a).reshape(t, h, hd), pos,
                      theta))
        k, v = (jnp.repeat(z, rep, axis=1) for z in kv(key, x, layer, pos, a))
        s = jnp.einsum("qhd,khd->hqk", operand(q), operand(k),
                       precision="highest") * hd ** -0.5
        qp, kp = pos[:, None], pos[None, :]
        seen = (kp <= qp) & ((kp < sink) | (qp - kp < window))
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", p, operand(v), precision="highest")
        x = rnd(x + linear(key, "wo", layer, rnd(o).reshape(t, h * hd)))
        a = rnd(_rmsnorm(x, tensor(key, cfg, "norm2", layer), eps))
        g = linear(key, "w_gate", layer, a)
        u = linear(key, "w_in", layer, a)
        m = rnd(jax.nn.silu(g) * u)
        return rnd(x + linear(key, "w_out", layer, m))

    def head(key):
        if cfg["tie_word_embeddings"]:
            return tensor(key, cfg, "embed", None)[:vocab].T
        return tensor(key, cfg, "head", None)[:, :vocab]

    @jax.jit
    def forward(key, tokens):
        pos = jnp.arange(tokens.shape[0])
        x = tensor(key, cfg, "embed", None)[tokens].astype(jnp.float32)

        def body(x, layer):
            return layer_fn(key, x, layer, pos), None
        x, _ = jax.lax.scan(body, x, jnp.arange(n_layers))
        f = operand(_rmsnorm(x, tensor(key, cfg, "final_norm", None), eps))
        return jnp.dot(f, head(key).astype(jnp.float32), precision="highest")

    @jax.jit
    def kv0(key, tokens, pos):
        x = tensor(key, cfg, "embed", None)[tokens].astype(jnp.float32)
        return kv(key, x, 0, pos, norm1(key, x))

    @functools.partial(jax.jit, static_argnums=5)
    def kv0_error(key, tokens, pos, k_in, v_in, ulps):
        emb = tensor(key, cfg, "embed", None)
        w = {n: weight(key, n, 0) for n in ("wk", "wv")}

        def chunk(args):
            tok, p, kc, vc = args
            x = emb[tok].astype(jnp.float32)
            a = norm1(key, x)
            k_ref, v_ref = kv(key, x, 0, p, a)
            free = _near_ties(a, block, keep, ulps, jnp.finfo(dt).nmant)
            _, idx = jax.lax.top_k(free.astype(jnp.float32),
                                   min(MAX_FREE, free.shape[-1]))
            ok = jnp.take_along_axis(free, idx, -1)
            coef = jnp.take_along_axis(a, idx, -1) * ok
            out = []
            for n, got, ref in (("wk", kc, k_ref), ("wv", vc, v_ref)):
                wm, scale = w[n]
                dirs = coef[..., None] * wm[idx] * scale       # (C, F, N)
                if n == "wk":
                    c, f = idx.shape
                    dirs = _rope(dirs.reshape(c * f, -1, hd),
                                 jnp.repeat(p, f), theta).reshape(c, f, -1)
                r = (got - ref).reshape(got.shape[0], -1)
                gram = jnp.einsum("cfn,cgn->cfg", dirs, dirs,
                                  precision="highest")
                ridge = (1e-6 * jnp.max(jnp.diagonal(gram, 0, 1, 2), -1,
                                        keepdims=True) + 1e-30 + (~ok))
                gram = gram + jax.vmap(jnp.diag)(ridge)
                rhs = jnp.einsum("cfn,cn->cf", dirs, r, precision="highest")
                sol = jnp.linalg.solve(gram, rhs[..., None])[..., 0]
                r = r - jnp.einsum("cf,cfn->cn", sol, dirs,
                                   precision="highest")
                out.append(jnp.linalg.norm(r, axis=-1) / jnp.linalg.norm(
                    ref.reshape(ref.shape[0], -1), axis=-1))
            return jnp.maximum(*out), free.sum(-1)

        def split(z):
            return z.reshape((-1, CHUNK) + z.shape[1:])
        err, nfree = jax.lax.map(chunk, tuple(map(split, (tokens, pos, k_in,
                                                          v_in))))
        return err.reshape(-1), nfree.reshape(-1)

    return forward, kv0, kv0_error


@functools.lru_cache(maxsize=4)
def _cached(cfg_json: str, act_dtype: str | None):
    return forward_fn(json.loads(cfg_json), act_dtype)


def _fns(cfg: dict, act_dtype: str | None = None):
    return _cached(json.dumps(cfg, sort_keys=True), act_dtype)


def logits(key, cfg: dict, seq: np.ndarray, pad_to: int,
           act_dtype: str | None = None) -> np.ndarray:
    """Reference logits (len(seq), V) for one sequence, padded to `pad_to`
    positions so every sequence reuses one compiled pass (causal: the pad
    after the sequence changes nothing before it)."""
    import jax
    toks = np.zeros(pad_to, np.int32)
    toks[:len(seq)] = seq
    with jax.default_matmul_precision("highest"):
        out = _fns(cfg, act_dtype)[0](key, toks)
    return np.asarray(jax.device_get(out))[:len(seq)]


def first_layer_kv(key, cfg: dict, tokens: np.ndarray, pos: np.ndarray,
                   act_dtype: str | None = None):
    """Reference K and V of the first layer, (P, kv heads, head dim) f32
    (values of the configuration's dtype), for `tokens` at positions `pos`:
    a position's first-layer K and V depend on its own token only."""
    import jax
    with jax.default_matmul_precision("highest"):
        k, v = _fns(cfg, act_dtype)[1](key, np.asarray(tokens, np.int32),
                                       np.asarray(pos, np.int32))
    return np.asarray(k), np.asarray(v)


def first_layer_error(key, cfg: dict, tokens: np.ndarray, pos: np.ndarray,
                      k: np.ndarray, v: np.ndarray, ulps: int = TIE_ULPS):
    """Per position, how far the given first-layer K and V lie from the
    reference's: |K - K_ref| / |K_ref| (the larger of K's and V's) after the
    residual's component along every near-tie lane's contribution is
    projected out.  A near-tie lane (``_near_ties``) is one whose side of
    DAS's cut a rounding of `ulps` ulps (of the configuration's dtype) in
    the normed activations can change; its contribution to K (roped) and V is a_lane * W[lane] *
    scale.  A sound program that kept the other lane of a near tie reads
    rounding only; a lower precision moves every lane, and the few free
    directions take little of it.  Also the count of near-tie lanes per
    position (at most ``MAX_FREE`` are projected out)."""
    import jax
    n = len(tokens)
    m = -(-n // CHUNK) * CHUNK

    def pad(z, dtype):
        z = np.asarray(z, dtype)
        return np.concatenate([z, np.repeat(z[:1], m - n, 0)]) if m > n else z
    with jax.default_matmul_precision("highest"):
        err, free = _fns(cfg)[2](key, pad(tokens, np.int32),
                                 pad(pos, np.int32), pad(k, np.float32),
                                 pad(v, np.float32), int(ulps))
    return np.asarray(err)[:n], np.asarray(free)[:n]
