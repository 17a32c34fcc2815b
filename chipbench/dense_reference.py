"""Plain float32 reference of the dense GQA decoder that the benchmark's
configurations run (the Qwen2 and Qwen3 layer equations), and the maker of
the weights that the program serves and this reference reads.

It imports nothing of the program. The weights are the benchmark's own:
`make_weights` draws them from the run's seed on the device, in one jitted
call, in the dtype they are served in, laid out as the program's parameter
tree (a test holds the layout to `jax.eval_shape` of the program's
`init_params`), on one device or, given their shardings, over a serving
mesh. The reference reads those values and computes in
float32 with every matrix product at `Precision.HIGHEST`, one layer at a
time, so that it fits beside them on one chip.

Layer equations (Qwen2 / Qwen3, as published in their `config.json` and
modelling code): RMSNorm `x * rsqrt(mean(x^2) + eps) * w`; q/k/v projections
with a bias (Qwen2) or per-head RMSNorm on q and k (Qwen3); rotary embedding
on the two halves of each head, `theta ** (-2i / head_dim)`; causal grouped
attention scaled by `head_dim ** -0.5`; output projection; SiLU-gated MLP;
final RMSNorm; LM head (the embedding, transposed, where it is tied).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _dims(conf: dict):
    return (conf["num_hidden_layers"], conf["hidden_size"],
            conf["num_attention_heads"], conf["num_key_value_heads"],
            conf["head_dim"], conf["intermediate_size"], conf["vocab_size"])


def param_shapes(conf: dict) -> dict:
    """The parameter tree the program serves, as nested dicts of shapes."""
    L, d, nq, nkv, hd, ff, V = _dims(conf)
    attn = {"wq": (L, d, nq, hd), "wk": (L, d, nkv, hd),
            "wv": (L, d, nkv, hd), "wo": (L, nq, hd, d)}
    if conf["attention_bias"]:
        attn.update(bq=(L, nq, hd), bk=(L, nkv, hd), bv=(L, nkv, hd))
    if conf["qk_norm"]:
        attn.update(q_norm=(L, hd), k_norm=(L, hd))
    tree = {"embed": (V, d), "final_norm": {"w": (d,)},
            "layers": {"attn_norm": {"w": (L, d)}, "attn": attn,
                       "mlp_norm": {"w": (L, d)},
                       "mlp": {"w_gate": (L, d, ff), "w_up": (L, d, ff),
                               "w_down": (L, ff, d)}}}
    if not conf["tie_word_embeddings"]:
        tree["lm_head"] = (d, V)
    return tree


def _draw(conf: dict, key) -> dict:
    """Every leaf drawn in the served dtype: matrices N(0, 0.02) (output
    projections scaled by 1/sqrt(2 L)), biases N(0, 0.02), norm weights
    1 + N(0, 0.1)."""
    L = conf["num_hidden_layers"]
    dt = jnp.dtype(conf["torch_dtype"])
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(conf), is_leaf=lambda s: isinstance(s, tuple))
    keys = jax.random.split(key, len(flat))
    out = []
    for (path, shape), k in zip(flat, keys):
        path = jax.tree_util.keystr(path)
        z = jax.random.normal(k, shape, dt)
        if "norm" in path:
            a = 1.0 + 0.1 * z
        elif "wo" in path or "w_down" in path:
            a = z * (0.02 / math.sqrt(2 * L))
        else:
            a = z * 0.02
        out.append(a.astype(dt))
    return jax.tree.unflatten(treedef, out)


def seed_key(seed: int):
    """A PRNG key for any seed below 2**62 (the driver's seeds pass 2**31)."""
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def make_weights(conf: dict, seed: int, shardings=None) -> dict:
    """The weights of `seed`. With `shardings`, a tree of shardings that
    mirrors the weights (a serving mesh's layout), each leaf is drawn
    straight into its sharding, so that no device ever holds the whole
    tree; the values are those of the draw without it (random bits do not
    depend on the layout)."""
    kw = {} if shardings is None else {"out_shardings": shardings}
    return jax.jit(lambda k: _draw(conf, k), **kw)(seed_key(seed))



# ------------------------------------------------------ lower precision ---


def _int8(w, axes):
    """Weight-only int8 with one scale per output channel (`axes` are the
    contracted axes): the round trip a quantized server would read."""
    s = jnp.max(jnp.abs(w), axis=axes, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(w / s), -127, 127) * s


def _fp8(w, axes):
    """float8 e4m3 with one scale per output channel."""
    s = jnp.max(jnp.abs(w), axis=axes, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


QUANT = {"int8": _int8, "fp8": _fp8}


def _w(a, quant, axes):
    a = a.astype(jnp.float32)
    return a if quant is None else QUANT[quant](a, axes)


# ------------------------------------------------------------- forward ---


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x (B, S, H, hd) at positions 0..S-1."""
    hd = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(conf, quant, x, lp):
    eps, theta = conf["rms_norm_eps"], float(conf["rope_theta"])
    B, S, _ = x.shape
    a = lp["attn"]
    h = _rms(x, lp["attn_norm"]["w"].astype(jnp.float32), eps)
    q = jnp.einsum("bsd,dhe->bshe", h, _w(a["wq"], quant, 0), precision=HIGHEST)
    k = jnp.einsum("bsd,dhe->bshe", h, _w(a["wk"], quant, 0), precision=HIGHEST)
    v = jnp.einsum("bsd,dhe->bshe", h, _w(a["wv"], quant, 0), precision=HIGHEST)
    if conf["attention_bias"]:
        q = q + a["bq"].astype(jnp.float32)
        k = k + a["bk"].astype(jnp.float32)
        v = v + a["bv"].astype(jnp.float32)
    if conf["qk_norm"]:
        q = _rms(q, a["q_norm"].astype(jnp.float32), eps)
        k = _rms(k, a["k_norm"].astype(jnp.float32), eps)
    q, k = _rope(q, theta), _rope(k, theta)
    nkv, hd = k.shape[2], k.shape[3]
    qg = q.reshape(B, S, nkv, -1, hd)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k, precision=HIGHEST) * hd ** -0.5
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", jax.nn.softmax(s, axis=-1), v,
                   precision=HIGHEST).reshape(B, S, -1, hd)
    x = x + jnp.einsum("bshe,hed->bsd", o, _w(a["wo"], quant, (0, 1)),
                       precision=HIGHEST)
    m = lp["mlp"]
    h = _rms(x, lp["mlp_norm"]["w"].astype(jnp.float32), eps)
    g = jnp.einsum("bsd,df->bsf", h, _w(m["w_gate"], quant, 0), precision=HIGHEST)
    u = jnp.einsum("bsd,df->bsf", h, _w(m["w_up"], quant, 0), precision=HIGHEST)
    x = x + jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u,
                       _w(m["w_down"], quant, 0), precision=HIGHEST)
    return x, None


def _vocab_blocks(V: int) -> int:
    return next(n for n in (8, 4, 2, 1) if V % n == 0)


def logits_at(conf: dict, w: dict, tokens, positions, quant=None):
    """float32 logits (B, P, V) at `positions` (B, P) of the sequences
    `tokens` (B, S). `quant` ("int8" or "fp8") computes every matrix product
    with weights rounded to that precision: the benchmark's control, the
    reference one precision below the served bfloat16."""
    eps = conf["rms_norm_eps"]
    embed = w["embed"]
    tied = conf["tie_word_embeddings"]
    x = _w(jnp.take(embed, tokens, axis=0), quant, -1)   # per-row scales
    x, _ = jax.lax.scan(lambda c, lp: _layer(conf, quant, c, lp), x,
                        w["layers"])
    x = jnp.take_along_axis(x, positions[..., None], axis=1)
    x = _rms(x, w["final_norm"]["w"].astype(jnp.float32), eps)
    V = conf["vocab_size"]
    nb = _vocab_blocks(V)
    vb = V // nb

    def block(j):                    # one slice of the vocabulary at a time
        if tied:
            hb = jax.lax.dynamic_slice_in_dim(embed, j * vb, vb, axis=0)
            return jnp.einsum("bpd,vd->bpv", x, _w(hb, quant, 1),
                              precision=HIGHEST)
        hb = jax.lax.dynamic_slice_in_dim(w["lm_head"], j * vb, vb, axis=1)
        return jnp.einsum("bpd,dv->bpv", x, _w(hb, quant, 0),
                          precision=HIGHEST)
    out = jax.lax.map(block, jnp.arange(nb))           # (nb, B, P, vb)
    return jnp.moveaxis(out, 0, 2).reshape(x.shape[0], x.shape[1], V)


def _readings(ref, logits, targets):
    """Per position: how far the reference's logit of the target token lies
    below its best ("gap"), the widest distance between `logits` and the
    reference's over the vocabulary ("diff"), and the mean square of that
    distance ("sq")."""
    best = ref.max(-1)
    d = logits - ref
    return {"gap": best - jnp.take_along_axis(ref, targets[..., None],
                                              -1)[..., 0],
            "diff": jnp.abs(d).max(-1), "sq": jnp.mean(d * d, axis=-1)}


def readings(conf: dict, w: dict, tokens, positions, targets, served,
             quants=()):
    """Readings of `_readings` at `positions` (B, P) of the sequences
    `tokens` (B, S): "served" for the served `targets` and their logits
    `served` (B, P, V) as the program computed them; and, for each precision
    in `quants`, for the reference computed in that precision in the
    program's place, with the tokens it puts first as targets."""
    ref = logits_at(conf, w, tokens, positions)
    out = {"served": _readings(ref, served, targets)}
    for q in quants:
        low = logits_at(conf, w, tokens, positions, quant=q)
        out[q] = _readings(ref, low, low.argmax(-1))
    return out


def compare_requests(conf: dict, w: dict, seqs: list, *, seq_len: int,
                     batch: int = 4, quants=()) -> dict:
    """`seqs` is a list of (prompt, served tokens, the program's float32
    logits of each served token (n, V)). Runs the reference over each
    prompt with its served tokens, in batches of `batch` rows padded to
    `seq_len`, and returns, for the program ("served") and each precision
    of `quants`, the widest gap and logit distance and the root mean square
    distance over every served token, with the number of tokens compared."""
    fn = jax.jit(lambda w, t, p, g, s: readings(conf, w, t, p, g, s, quants))
    width = max(len(out) for _, out, _ in seqs)
    V = conf["vocab_size"]
    acc: dict = {}
    n_tokens = 0
    for i in range(0, len(seqs), batch):
        rows = seqs[i:i + batch]
        toks = np.zeros((batch, seq_len), np.int32)
        pos = np.zeros((batch, width), np.int32)
        tgt = np.zeros((batch, width), np.int32)
        served = np.zeros((batch, width, V), np.float32)
        mask = np.zeros((batch, width), bool)
        for r, (prompt, out, logits) in enumerate(rows):
            full = list(prompt) + list(out)
            toks[r, :len(full)] = full
            n = len(out)
            pos[r, :n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
            tgt[r, :n] = out
            served[r, :n] = logits
            mask[r, :n] = True
        got = fn(w, jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(tgt),
                 jnp.asarray(served))
        n_tokens += int(mask.sum())
        for name, rd in got.items():
            a = acc.setdefault(name, {"gap": -np.inf, "diff": -np.inf,
                                      "sq": 0.0})
            for k in ("gap", "diff"):
                a[k] = max(a[k], float(np.asarray(rd[k])[mask].max()))
            a["sq"] += float(np.asarray(rd["sq"], np.float64)[mask].sum())
    return {"tokens": n_tokens,
            **{name: {"gap": a["gap"], "diff": a["diff"],
                      "rms": math.sqrt(a["sq"] / n_tokens)}
               for name, a in acc.items()}}
