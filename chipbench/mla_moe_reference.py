"""Plain float32 reference of DeepSeek-V2's decoder (multi-head latent
attention and the mixture-of-experts layer) as one chip of an
expert-parallel deployment computes it, and the maker of the weights that
the program serves and this reference reads.

It imports nothing of the program. `make_weights` draws the weights from the
run's seed on the device, in one jitted call, in the dtype they are served
in, laid out as the program's parameter tree (a test holds the layout to
`jax.eval_shape` of the program's `init_params`). The reference reads those
values and computes in float32 with every matrix product at
`Precision.HIGHEST`, one layer at a time, and the LM head in vocabulary
blocks, so that it fits beside them on one chip.

Layer equations (DeepSeek-V2, `modeling_deepseek.py` and its
`config.json`), each as published unless noted:

- RMSNorm `x * rsqrt(mean(x^2) + eps) * w`.
- Attention (MLA, no query compression), in its textbook form: q =
  x Wq split into a 128-dim non-rotary and a 64-dim rotary part per head;
  [c_kv, k_rope] = x W_dkv; c_kv RMS-normed; per head k_nope = c_kv W_uk and
  v = c_kv W_uv (explicit up-projections, where the program folds them into
  the query and context sides); k_rope shared by the heads; YaRN rotary
  embedding on q_rope and k_rope; causal softmax of (q_nope.k_nope +
  q_rope.k_rope) times `(qk_nope + qk_rope) ** -0.5 * mscale ** 2`, with
  `mscale = 0.1 * mscale_all_dim * ln(factor) + 1`; output projection.
  Departure: the rotary dimensions rotate as two halves, where DeepSeek-V2
  rotates interleaved pairs (a fixed permutation of the rotary columns of
  Wq and W_dkv, immaterial with random weights), as the program does.
- YaRN (`DeepseekV2YarnRotaryEmbedding`): per rotary frequency pair i of
  `dim`, the base inverse frequency `theta ** (-2i / dim)`, or that over
  `factor`, blended by a ramp that is 0 below the dimension that turns
  `beta_fast` times over `original_max_position_embeddings` and 1 above the
  one that turns `beta_slow` times; cos and sin scaled by `mscale(mscale) /
  mscale(mscale_all_dim)`.
- The first `first_k_dense_replace` layers: a SiLU-gated MLP.
- The expert layers: the gate scores every one of the published
  `published_n_routed_experts` experts in float32 from float32 inputs,
  softmax, greedy top `num_experts_per_tok`, renormalised only where
  `norm_topk_prob`, times `routed_scaling_factor`. The chip holds the
  `n_routed_experts` experts from `held_expert_start`: each adds its
  SiLU-gated output times the token's gate weight for it; the experts held
  elsewhere add nothing here, as in the program. The shared experts (one
  SiLU-gated MLP of `n_shared_experts` times the expert width) add theirs.
- Final RMSNorm; untied LM head.

Routing near ties: a token whose 6th and 7th gate probabilities lie close
may pick another 6th expert in bfloat16 than in float32. The reference
routes on its own hidden states only. To count such choices it also runs
its equations with every matrix product's operands and result rounded to
bfloat16, the served dtype, and counts the (position, expert layer) pairs
whose top-k set differs from its own (`compare_requests`' "route_flips").
"""
from __future__ import annotations

import math
import sys

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.dense_reference import (QUANT, _readings, _rms, _vocab_blocks,
                                       seed_key)

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def _moe_layers(conf: dict) -> int:
    return conf["num_hidden_layers"] - conf["first_k_dense_replace"]


def param_shapes(conf: dict) -> dict:
    """The parameter tree the program serves, as nested dicts of shapes."""
    nd, nm = conf["first_k_dense_replace"], _moe_layers(conf)
    d, H, V = (conf["hidden_size"], conf["num_attention_heads"],
               conf["vocab_size"])
    r, qn, qr, vd = (conf["kv_lora_rank"], conf["qk_nope_head_dim"],
                     conf["qk_rope_head_dim"], conf["v_head_dim"])
    ff, eff = conf["intermediate_size"], conf["moe_intermediate_size"]
    E, n = conf["published_n_routed_experts"], conf["n_routed_experts"]
    sh = conf["n_shared_experts"] * eff

    def block(L):
        return {"attn_norm": {"w": (L, d)}, "mlp_norm": {"w": (L, d)},
                "attn": {"wq": (L, d, H, qn + qr), "w_dkv": (L, d, r + qr),
                         "w_uk": (L, r, H, qn), "w_uv": (L, r, H, vd),
                         "wo": (L, H, vd, d), "kv_norm": (L, r)}}
    dense = block(nd)
    dense["mlp"] = {"w_gate": (nd, d, ff), "w_up": (nd, d, ff),
                    "w_down": (nd, ff, d)}
    moe = block(nm)
    moe["moe"] = {"router": (nm, d, E), "w_gate": (nm, n, d, eff),
                  "w_up": (nm, n, d, eff), "w_down": (nm, n, eff, d),
                  "shared": {"w_gate": (nm, d, sh), "w_up": (nm, d, sh),
                             "w_down": (nm, sh, d)}}
    return {"embed": (V, d), "final_norm": {"w": (d,)}, "lm_head": (d, V),
            "dense_layers": dense, "layers": moe}


def _draw(conf: dict, key) -> dict:
    """Every leaf drawn in the served dtype: matrices N(0, 0.02) (output
    projections scaled by 1/sqrt(2 L)), norm weights 1 + N(0, 0.1)."""
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


def make_weights(conf: dict, seed: int, shardings=None) -> dict:
    """The weights of `seed`; with `shardings` (a tree mirroring the
    weights), each leaf is drawn straight into its sharding."""
    kw = {} if shardings is None else {"out_shardings": shardings}
    return jax.jit(lambda k: _draw(conf, k), **kw)(seed_key(seed))


# ------------------------------------------------------------- forward ---


def _w(a, quant, axes):
    """A weight in float32, rounded to `quant` ("int8", "fp8") with one
    scale per output channel (`axes` are the contracted axes), or as
    served."""
    a = a.astype(F32)
    return a if quant is None else QUANT[quant](a, axes)


def _mm(spec, a, w, act):
    """One matrix product at HIGHEST precision; in the bfloat16 shadow
    (`act` bfloat16) its operands and result are rounded to bfloat16."""
    out = jnp.einsum(spec, a.astype(act), w.astype(act), precision=HIGHEST,
                     preferred_element_type=F32)
    return out.astype(act).astype(F32)


def _yarn_mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def _rope_tables(conf: dict, S: int):
    """cos and sin (S, rope_dim / 2) of the YaRN rotary embedding."""
    dim, base = conf["qk_rope_head_dim"], float(conf["rope_theta"])
    sc = conf["rope_scaling"]
    factor = float(sc["factor"])
    orig = sc["original_max_position_embeddings"]

    def turns_dim(n):     # the dimension that turns n times over `orig`
        return dim * math.log(orig / (n * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(turns_dim(sc["beta_fast"])), 0)
    high = min(math.ceil(turns_dim(sc["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    i = np.arange(0, dim, 2, dtype=np.float64)
    extra = 1.0 / base ** (i / dim)
    inter = 1.0 / (factor * base ** (i / dim))
    keep = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    inv = inter * (1 - keep) + extra * keep
    m = _yarn_mscale(factor, sc["mscale"]) / \
        _yarn_mscale(factor, sc["mscale_all_dim"])
    ang = jnp.arange(S, dtype=F32)[:, None] * jnp.asarray(inv, F32)
    return jnp.cos(ang) * m, jnp.sin(ang) * m


def _rope(x, cos, sin):
    """x (B, S, H, dim), its two halves rotated."""
    c, s = cos[None, :, None], sin[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def softmax_scale(conf: dict) -> float:
    scale = (conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"]) ** -0.5
    sc = conf["rope_scaling"]
    if sc.get("mscale_all_dim"):
        scale *= _yarn_mscale(float(sc["factor"]), sc["mscale_all_dim"]) ** 2
    return scale


def _attention(conf, quant, act, x, lp, rope):
    eps = conf["rms_norm_eps"]
    qn, r = conf["qk_nope_head_dim"], conf["kv_lora_rank"]
    S = x.shape[1]
    a = lp["attn"]
    h = _rms(x, lp["attn_norm"]["w"].astype(F32), eps)
    q = _mm("bsd,dhe->bshe", h, _w(a["wq"], quant, 0), act)
    q_nope, q_rope = q[..., :qn], q[..., qn:]
    dkv = _mm("bsd,dr->bsr", h, _w(a["w_dkv"], quant, 0), act)
    c_kv = _rms(dkv[..., :r], a["kv_norm"].astype(F32), eps).astype(act)
    k_nope = _mm("bsr,rhe->bshe", c_kv, _w(a["w_uk"], quant, 0), act)
    v = _mm("bsr,rhe->bshe", c_kv, _w(a["w_uv"], quant, 0), act)
    q_rope = _rope(q_rope, *rope)
    k_rope = _rope(dkv[..., None, r:], *rope)              # one head, shared
    s = (jnp.einsum("bqhe,bkhe->bhqk", q_nope, k_nope, precision=HIGHEST)
         + jnp.einsum("bqhe,bkxe->bhqk", q_rope, k_rope, precision=HIGHEST))
    s = s * softmax_scale(conf)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhe->bqhe", p, v, precision=HIGHEST)
    return x + _mm("bshe,hed->bsd", o, _w(a["wo"], quant, (0, 1)), act)


def _swiglu(h, wg, wu, wd, quant, act):
    g = _mm("bsd,df->bsf", h, _w(wg, quant, 0), act)
    u = _mm("bsd,df->bsf", h, _w(wu, quant, 0), act)
    return _mm("bsf,fd->bsd", jax.nn.silu(g) * u, _w(wd, quant, 0), act)


def _dense_layer(conf, quant, act, x, lp, rope):
    x = _attention(conf, quant, act, x, lp, rope)
    h = _rms(x, lp["mlp_norm"]["w"].astype(F32), conf["rms_norm_eps"])
    m = lp["mlp"]
    return x + _swiglu(h, m["w_gate"], m["w_up"], m["w_down"], quant, act)


def route(conf: dict, h, router):
    """Gate weights (B, S, K) and expert ids (B, S, K) of every token over
    all published experts."""
    logits = jnp.einsum("bsd,de->bse", h, router.astype(F32),
                        precision=HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, conf["num_experts_per_tok"])
    if conf["norm_topk_prob"]:
        top_w = top_w / top_w.sum(-1, keepdims=True)
    return top_w * conf["routed_scaling_factor"], top_i


def _moe_layer(conf, quant, act, x, lp, rope):
    x = _attention(conf, quant, act, x, lp, rope)
    h = _rms(x, lp["mlp_norm"]["w"].astype(F32),
             conf["rms_norm_eps"]).astype(act).astype(F32)
    m = lp["moe"]
    top_w, top_i = route(conf, h, _w(m["router"], quant, 0))
    e0, n = conf["held_expert_start"], conf["n_routed_experts"]
    # each held expert's gate weight for each token (0 where not picked)
    gate = (jax.nn.one_hot(top_i - e0, n, dtype=F32)
            * top_w[..., None]).sum(-2)                     # (B, S, n)
    g = _mm("bsd,edf->bsef", h, _w(m["w_gate"], quant, 1), act)
    u = _mm("bsd,edf->bsef", h, _w(m["w_up"], quant, 1), act)
    y = _mm("bsef,efd->bsd", jax.nn.silu(g) * u * gate[..., None],
            _w(m["w_down"], quant, 1), act)
    s = m["shared"]
    y = y + _swiglu(h, s["w_gate"], s["w_up"], s["w_down"], quant, act)
    return x + y, top_i


def logits_at(conf: dict, w: dict, tokens, positions, quant=None, act=F32):
    """float32 logits (B, P, V) at `positions` (B, P) of the sequences
    `tokens` (B, S), and each token's expert ids (expert layers, B, S, K).
    `quant` ("int8" or "fp8") rounds every weight to that precision, the
    benchmark's control; `act` bfloat16 runs the bfloat16 shadow."""
    S = tokens.shape[1]
    rope = _rope_tables(conf, S)
    x = _w(jnp.take(w["embed"], tokens, axis=0), quant, -1)   # per-row scales
    x, _ = jax.lax.scan(
        lambda c, lp: (_dense_layer(conf, quant, act, c, lp, rope), None),
        x, w["dense_layers"])
    x, top_i = jax.lax.scan(
        lambda c, lp: _moe_layer(conf, quant, act, c, lp, rope),
        x, w["layers"])
    x = jnp.take_along_axis(x, positions[..., None], axis=1)
    x = _rms(x, w["final_norm"]["w"].astype(F32), conf["rms_norm_eps"])
    V = conf["vocab_size"]
    nb = _vocab_blocks(V)
    vb = V // nb

    def block(j):                    # one slice of the vocabulary at a time
        hb = jax.lax.dynamic_slice_in_dim(w["lm_head"], j * vb, vb, axis=1)
        return jnp.einsum("bpd,dv->bpv", x, _w(hb, quant, 0),
                          precision=HIGHEST)
    out = jax.lax.map(block, jnp.arange(nb))           # (nb, B, P, vb)
    return (jnp.moveaxis(out, 0, 2).reshape(x.shape[0], x.shape[1], V),
            top_i)


def readings(conf: dict, w: dict, tokens, positions, targets, served, valid,
             quants=()):
    """`dense_reference._readings` of the program ("served") and of each
    precision of `quants` at `positions` (B, P) of `tokens` (B, S). With
    controls (`quants`) also "flips": the (position, expert layer) pairs
    among the `valid` (B, S) positions whose top-k experts differ between
    the reference and its bfloat16 shadow, a third pass that the timed run
    (no controls) does not pay for."""
    ref, top = logits_at(conf, w, tokens, positions)
    out = {"served": _readings(ref, served, targets)}
    for q in quants:
        low, _ = logits_at(conf, w, tokens, positions, quant=q)
        out[q] = _readings(ref, low, low.argmax(-1))
    if quants:
        _, top16 = logits_at(conf, w, tokens, positions,
                             act=jnp.dtype(conf["torch_dtype"]))
        differ = (jnp.sort(top, -1) != jnp.sort(top16, -1)).any(-1)
        out["flips"] = (differ & valid[None]).sum()         # (Lm, B, S)
    return out


def compare_requests(conf: dict, w: dict, seqs: list, *, seq_len: int,
                     batch: int = 4, quants=()) -> dict:
    """`seqs` is a list of (prompt, served tokens, the program's float32
    logits of each served token (n, V)). Runs the reference over each
    prompt with its served tokens, in batches of `batch` rows padded to
    `seq_len`, and returns, for the program ("served") and each precision
    of `quants`, the widest gap and logit distance and the root mean square
    distance over every served token, with the number of tokens compared,
    and, with controls, "route_flips": [pairs whose top-k differs in the
    bfloat16 shadow, (position, expert layer) pairs compared]."""
    fn = jax.jit(lambda w, t, p, g, s, m: readings(conf, w, t, p, g, s, m,
                                                   quants))
    width = max(len(out) for _, out, _ in seqs)
    V = conf["vocab_size"]
    acc: dict = {}
    n_tokens = flips = pairs = 0
    for i in range(0, len(seqs), batch):
        rows = seqs[i:i + batch]
        toks = np.zeros((batch, seq_len), np.int32)
        pos = np.zeros((batch, width), np.int32)
        tgt = np.zeros((batch, width), np.int32)
        served = np.zeros((batch, width, V), np.float32)
        mask = np.zeros((batch, width), bool)
        valid = np.zeros((batch, seq_len), bool)
        for r, (prompt, out, logits) in enumerate(rows):
            full = list(prompt) + list(out)
            toks[r, :len(full)] = full
            valid[r, :len(full)] = True
            n = len(out)
            pos[r, :n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
            tgt[r, :n] = out
            served[r, :n] = logits
            mask[r, :n] = True
        with jax.default_matmul_precision("highest"):
            got = fn(w, jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(tgt),
                     jnp.asarray(served), jnp.asarray(valid))
        n_tokens += int(mask.sum())
        flips += int(got.pop("flips", 0))
        pairs += int(valid.sum()) * _moe_layers(conf)
        for name, rd in got.items():
            a = acc.setdefault(name, {"gap": -np.inf, "diff": -np.inf,
                                      "sq": 0.0})
            for k in ("gap", "diff"):
                a[k] = max(a[k], float(np.asarray(rd[k])[mask].max()))
            a["sq"] += float(np.asarray(rd["sq"], np.float64)[mask].sum())
    out = {"tokens": n_tokens}
    if quants:
        print(f"reference: {flips} of {pairs} (position, expert layer) pairs "
              f"route to another top-k set in bfloat16", file=sys.stderr,
              flush=True)
        out["route_flips"] = [flips, pairs]
    return {**out, **{name: {"gap": a["gap"], "diff": a["diff"],
                      "rms": math.sqrt(a["sq"] / n_tokens)}
               for name, a in acc.items()}}
