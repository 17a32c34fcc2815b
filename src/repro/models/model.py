"""Model assembly: init / forward / prefill / decode for all six families.

Families: dense | moe | ssm | hybrid | encdec | vlm — all driven by one
ModelConfig. Layer stacks are *stacked pytrees* scanned with lax.scan so HLO
size and compile time are depth-independent (a 95-layer deepseek compiles
like one layer), and remat has a natural per-layer boundary.

Inputs (`batch` dicts):
  dense/moe/ssm/hybrid : {"tokens": (B, S) int32}
  encdec (whisper)     : {"tokens": (B, S), "frames": (B, encoder_seq, d)}  # stub frontend
  vlm (llava)          : {"tokens": (B, S - n_image_tokens),
                          "image_embeds": (B, n_image_tokens, vision_dim)}  # stub frontend
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from .config import ModelConfig
from . import layers as L
from . import ssm as S

VISION_DIM = 1024  # stub vision-tower output width (llava)


def _cast(params, dtype):
    def c(a):
        return a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating) else a
    return jax.tree.map(c, params)


@jax.named_scope("lm_head")
def _logits(cfg, x, head):
    """LM head accumulated in float32 and returned in `cfg.logit_dtype`:
    greedy argmax over a bf16 vocabulary would tie (and flip between
    sharded and single-device runs) far more often."""
    out = jnp.einsum("...d,dv->...v", x, head,
                     preferred_element_type=jnp.float32)
    return out.astype(cfg.logit_dtype)


# ------------------------------------------------------------------ init ----


def _block_init(cfg: ModelConfig, key, *, moe: bool = False, cross: bool = False):
    ks = jax.random.split(key, 6)
    p = {
        "attn_norm": L.norm_init(cfg, cfg.d_model),
        "attn": L.mla_init(cfg, ks[0]) if cfg.use_mla else L.attn_init(cfg, ks[0]),
        "mlp_norm": L.norm_init(cfg, cfg.d_model),
    }
    if moe:
        p["moe"] = L.moe_init(cfg, ks[1])
    else:
        p["mlp"] = L.mlp_init(cfg, ks[1])
    if cross:
        p["cross_norm"] = L.norm_init(cfg, cfg.d_model)
        p["cross_attn"] = L.attn_init(cfg, ks[2])
    return p


def _stack_init(init_fn, key, n):
    keys = jax.random.split(key, n)
    return jax.vmap(init_fn)(keys)


@partial(jax.jit, static_argnums=0)
def init_params(cfg: ModelConfig, key):
    """Random params with every floating leaf in `cfg.dtype`. The draw is
    float32 and the cast happens inside this one jitted program, so a bf16
    deployment never holds a float32 copy of the tree on the device."""
    return _cast(_init_params_f32(cfg, key), cfg.dtype)


def _init_params_f32(cfg: ModelConfig, key):
    ks = jax.random.split(key, 8)
    p = {"embed": jax.random.normal(ks[0], (cfg.vocab_size, cfg.d_model), jnp.float32) * 0.02,
         "final_norm": L.norm_init(cfg, cfg.d_model)}
    if not cfg.tie_embeddings:
        p["lm_head"] = jax.random.normal(ks[1], (cfg.d_model, cfg.vocab_size), jnp.float32) * 0.02

    fam = cfg.family
    if fam in ("dense", "vlm"):
        p["layers"] = _stack_init(lambda k: _block_init(cfg, k), ks[2], cfg.num_layers)
        if fam == "vlm":
            k1, k2 = jax.random.split(ks[3])
            p["mm_proj"] = {
                "w1": jax.random.normal(k1, (VISION_DIM, cfg.d_model), jnp.float32) * 0.02,
                "w2": jax.random.normal(k2, (cfg.d_model, cfg.d_model), jnp.float32) * 0.02,
            }
    elif fam == "moe":
        nd = cfg.first_dense_layers
        if nd:
            p["dense_layers"] = _stack_init(lambda k: _block_init(cfg, k), ks[2], nd)
        p["layers"] = _stack_init(lambda k: _block_init(cfg, k, moe=True), ks[3],
                                  cfg.num_layers - nd)
    elif fam == "ssm":
        def mb(k):
            return {"norm": L.norm_init(cfg, cfg.d_model), "mamba": S.mamba1_init(cfg, k)}
        p["layers"] = _stack_init(mb, ks[2], cfg.num_layers)
    elif fam == "hybrid":
        def mb(k):
            return {"norm": L.norm_init(cfg, cfg.d_model), "mamba": S.mamba2_init(cfg, k)}
        p["layers"] = _stack_init(mb, ks[2], cfg.num_layers)
        p["shared_blocks"] = _stack_init(lambda k: _block_init(cfg, k), ks[3],
                                         cfg.n_shared_attn_blocks)
        n_app = cfg.num_layers // cfg.attn_every
        p["lora"] = L.lora_init(cfg, ks[4], n_app)
    elif fam == "encdec":
        p["enc_layers"] = _stack_init(lambda k: _block_init(cfg, k), ks[2], cfg.n_encoder_layers)
        p["enc_final_norm"] = L.norm_init(cfg, cfg.d_model)
        p["dec_layers"] = _stack_init(lambda k: _block_init(cfg, k, cross=True), ks[3],
                                      cfg.num_layers)
        p["dec_pos"] = jax.random.normal(ks[4], (cfg.max_position, cfg.d_model), jnp.float32) * 0.02
    else:
        raise ValueError(fam)
    return p


def param_count(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


# ----------------------------------------------------------- trunk fwd -----


def _dense_block(cfg, lp, x, positions, constrain, *, lora=None, causal=True):
    h = L.norm_apply(cfg, lp["attn_norm"], x)
    if cfg.use_mla:
        a, kv = L.mla_apply(cfg, lp["attn"], h, positions=positions)
    else:
        a, kv = L.attn_apply(cfg, lp["attn"], h, positions=positions, causal=causal,
                             lora=lora, constrain=constrain)
    x = constrain(x + a, "hidden")
    h = L.norm_apply(cfg, lp["mlp_norm"], x)
    if "moe" in lp:
        m, _, aux = L.moe_apply(cfg, lp["moe"], h, constrain)
    else:
        m, aux = L.mlp_apply(cfg, lp["mlp"], h, constrain), jnp.float32(0.0)
    return constrain(x + m, "hidden"), kv, aux


def _ffn(cfg, lp, h, constrain):
    """A block's second half, added to `h`: the norm, then the MLP or the
    expert layer. Returns (h, held): the expert layer's held assignments
    per token (B, S), None for an MLP."""
    hh = L.norm_apply(cfg, lp["mlp_norm"], h)
    if "moe" in lp:
        m, held, _ = L.moe_apply(cfg, lp["moe"], hh, constrain)
        return h + m, held
    return h + L.mlp_apply(cfg, lp["mlp"], hh, constrain), None


def _trunk(cfg, p, x, cache, new_cache, layer, scan):
    """Scan `layer(lp, h, c0, c1) -> (h, c0, c1, held)` over the decoder
    stack of a dense / vlm / moe model (a moe model's leading dense layers,
    then its expert layers), each layer with its slice of the
    length-indexed caches (`ckv`/`krope` for MLA, else `k`/`v`). Writes the
    updated caches into `new_cache`; returns (x, held): the held
    assignments (B, S) summed over the expert layers, or None."""
    keys = ("ckv", "krope") if cfg.use_mla else ("k", "v")

    def body(h, xs):
        lp, c0, c1 = xs
        h, c0, c1, held = layer(lp, h, c0, c1)
        return h, (c0, c1, held)

    nd = cfg.first_dense_layers if cfg.family == "moe" else 0
    stacks = [(p["dense_layers"], slice(0, nd)),
              (p["layers"], slice(nd, None))] if nd else [(p["layers"], None)]
    outs, held = [], None
    for stack, sl in stacks:
        c0, c1 = (cache[k] if sl is None else cache[k][sl] for k in keys)
        x, (c0, c1, h) = scan(body, x, (stack, c0, c1))
        outs.append((c0, c1))
        if h is not None:
            held = h.sum(0)
    for j, k in enumerate(keys):
        parts = [o[j] for o in outs]
        new_cache[k] = jnp.concatenate(parts, 0) if len(parts) > 1 else parts[0]
    return x, held


def _scan_blocks(cfg, stacked, x, positions, constrain, *, moe, remat, causal=True,
                 unroll=False):
    def body(carry, lp):
        h, aux = carry
        h, kv, a = _dense_block(cfg, lp, h, positions, constrain, causal=causal)
        return (h, aux + a), kv

    fn = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable) if remat else body
    (x, aux), kvs = lax.scan(fn, (x, jnp.float32(0.0)), stacked, unroll=unroll)
    return x, kvs, aux


def _ssm_block(cfg, lp, x, constrain):
    h = L.norm_apply(cfg, lp["norm"], x)
    if cfg.mamba_version == 2:
        y = S.mamba2_apply(cfg, lp["mamba"], h)
    else:
        y = S.mamba1_apply(cfg, lp["mamba"], h)
    return constrain(x + y, "hidden")


def _hybrid_trunk(cfg, p, x, positions, constrain, *, remat, unroll=False):
    """Zamba2: scan over super-blocks of (shared attn block + attn_every mamba)."""
    n_app = cfg.num_layers // cfg.attn_every
    stacked = jax.tree.map(
        lambda a: a.reshape((n_app, cfg.attn_every) + a.shape[1:]), p["layers"])

    def super_block(carry, inp):
        h, _ = carry
        i, mamba_stack, lora_i = inp
        shared = jax.tree.map(lambda a: a[i % cfg.n_shared_attn_blocks], p["shared_blocks"])
        h, _, _ = _dense_block(cfg, shared, h, positions, constrain, lora=lora_i)

        def mamba_body(hh, lp):
            return _ssm_block(cfg, lp, hh, constrain), None
        mb = jax.checkpoint(mamba_body, policy=jax.checkpoint_policies.nothing_saveable) if remat else mamba_body
        h, _ = lax.scan(mb, h, mamba_stack, unroll=unroll)
        return (h, jnp.float32(0.0)), None

    fn = jax.checkpoint(super_block, policy=jax.checkpoint_policies.nothing_saveable) if remat else super_block
    (x, _), _ = lax.scan(fn, (x, jnp.float32(0.0)),
                         (jnp.arange(n_app), stacked, p["lora"]), unroll=unroll)
    return x


def _encoder(cfg, p, frames, constrain, *, remat, unroll=False):
    x = frames + L.sinusoidal_positions(frames.shape[1], cfg.d_model).astype(frames.dtype)
    positions = jnp.arange(frames.shape[1])[None, :]

    def body(h, lp):
        hh = L.norm_apply(cfg, lp["attn_norm"], h)
        a, _ = L.attn_apply(cfg, lp["attn"], hh, positions=positions, causal=False)
        h = constrain(h + a, "hidden")
        hh = L.norm_apply(cfg, lp["mlp_norm"], h)
        return constrain(h + L.mlp_apply(cfg, lp["mlp"], hh), "hidden"), None

    fn = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable) if remat else body
    x, _ = lax.scan(fn, x, p["enc_layers"], unroll=unroll)
    return L.norm_apply(cfg, p["enc_final_norm"], x)


def _decoder_block(cfg, lp, x, positions, enc_out, constrain):
    h = L.norm_apply(cfg, lp["attn_norm"], x)
    a, kv = L.attn_apply(cfg, lp["attn"], h, positions=positions, causal=True)
    x = constrain(x + a, "hidden")
    h = L.norm_apply(cfg, lp["cross_norm"], x)
    ck = jnp.einsum("bsd,dhe->bshe", enc_out, lp["cross_attn"]["wk"])
    cv = jnp.einsum("bsd,dhe->bshe", enc_out, lp["cross_attn"]["wv"])
    a, _ = L.attn_apply(cfg, lp["cross_attn"], h, positions=positions, causal=False,
                        kv_override=(ck, cv))
    x = constrain(x + a, "hidden")
    h = L.norm_apply(cfg, lp["mlp_norm"], x)
    return constrain(x + L.mlp_apply(cfg, lp["mlp"], h), "hidden"), kv, (ck, cv)


# ------------------------------------------------------------- forward -----


def forward(cfg: ModelConfig, params, batch, *, remat=False, constrain=None,
            return_kv=False, unroll=False):
    """Full-sequence forward. Returns (logits, aux_loss) — logits (B, S, V)
    over *text* positions (vlm: image positions excluded)."""
    constrain = constrain or L.no_constrain
    p = _cast(params, cfg.dtype)
    tokens = batch["tokens"]
    B = tokens.shape[0]
    x = jnp.take(p["embed"], tokens, axis=0)
    aux = jnp.float32(0.0)
    kvs = None
    n_img = 0

    if cfg.family == "vlm":
        img = batch["image_embeds"].astype(cfg.dtype)
        img = jax.nn.gelu(img @ p["mm_proj"]["w1"]) @ p["mm_proj"]["w2"]
        x = jnp.concatenate([img, x], axis=1)
        n_img = img.shape[1]
    S_total = x.shape[1]
    positions = jnp.arange(S_total)[None, :]
    x = constrain(x, "hidden")

    if cfg.family in ("dense", "vlm"):
        x, kvs, aux = _scan_blocks(cfg, p["layers"], x, positions, constrain,
                                   moe=False, remat=remat, unroll=unroll)
    elif cfg.family == "moe":
        if cfg.first_dense_layers:
            x, _, _ = _scan_blocks(cfg, p["dense_layers"], x, positions, constrain,
                                   moe=False, remat=remat, unroll=unroll)
        x, kvs, aux = _scan_blocks(cfg, p["layers"], x, positions, constrain,
                                   moe=True, remat=remat, unroll=unroll)
    elif cfg.family == "ssm":
        def body(h, lp):
            return _ssm_block(cfg, lp, h, constrain), None
        fn = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable) if remat else body
        x, _ = lax.scan(fn, x, p["layers"], unroll=unroll)
    elif cfg.family == "hybrid":
        x = _hybrid_trunk(cfg, p, x, positions, constrain, remat=remat, unroll=unroll)
    elif cfg.family == "encdec":
        enc_out = _encoder(cfg, p, batch["frames"].astype(cfg.dtype), constrain,
                           remat=remat, unroll=unroll)
        pos_emb = lax.dynamic_slice_in_dim(p["dec_pos"], 0, tokens.shape[1], axis=0)
        x = x + pos_emb[None]

        def body(h, lp):
            h, kv, ckv = _decoder_block(cfg, lp, h, positions, enc_out, constrain)
            return h, (kv, ckv)
        fn = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable) if remat else body
        x, _ = lax.scan(fn, x, p["dec_layers"], unroll=unroll)

    x = L.norm_apply(cfg, p["final_norm"], x)
    if n_img:
        x = x[:, n_img:]
    head = p["embed"].T if cfg.tie_embeddings else p["lm_head"]
    logits = constrain(_logits(cfg, x, head), "logits")
    return logits, aux


# ------------------------------------------------------------ caches -------


def init_decode_cache(cfg: ModelConfig, B: int, max_len: int, dtype=None):
    """Decode-state pytree sized for a cache of `max_len` tokens."""
    dt = dtype or jnp.dtype(cfg.dtype)
    hd, nkv = cfg.resolved_head_dim, cfg.n_kv_heads
    cache = {"pos": jnp.zeros((), jnp.int32)}
    fam = cfg.family
    if fam in ("dense", "vlm"):
        Lc = cfg.num_layers
        if cfg.use_mla:
            cache["ckv"] = jnp.zeros((Lc, B, max_len, cfg.kv_lora_rank), dt)
            cache["krope"] = jnp.zeros((Lc, B, max_len, cfg.qk_rope_dim), dt)
        else:
            cache["k"] = jnp.zeros((Lc, B, max_len, nkv, hd), dt)
            cache["v"] = jnp.zeros((Lc, B, max_len, nkv, hd), dt)
    elif fam == "moe":
        Lc = cfg.num_layers
        if cfg.use_mla:
            cache["ckv"] = jnp.zeros((Lc, B, max_len, cfg.kv_lora_rank), dt)
            cache["krope"] = jnp.zeros((Lc, B, max_len, cfg.qk_rope_dim), dt)
        else:
            cache["k"] = jnp.zeros((Lc, B, max_len, nkv, hd), dt)
            cache["v"] = jnp.zeros((Lc, B, max_len, nkv, hd), dt)
    elif fam == "ssm":
        di = cfg.d_inner
        cache["conv"] = jnp.zeros((cfg.num_layers, B, cfg.ssm_conv - 1, di), dt)
        cache["ssm"] = jnp.zeros((cfg.num_layers, B, di, cfg.ssm_state), jnp.float32)
    elif fam == "hybrid":
        conv_dim = cfg.d_inner + 2 * cfg.ssm_state
        n_app = cfg.num_layers // cfg.attn_every
        cache["conv"] = jnp.zeros((cfg.num_layers, B, cfg.ssm_conv - 1, conv_dim), dt)
        cache["ssm"] = jnp.zeros((cfg.num_layers, B, cfg.n_ssm_heads,
                                  cfg.mamba_headdim, cfg.ssm_state), jnp.float32)
        cache["k"] = jnp.zeros((n_app, B, max_len, nkv, hd), dt)
        cache["v"] = jnp.zeros((n_app, B, max_len, nkv, hd), dt)
    elif fam == "encdec":
        Lc = cfg.num_layers
        cache["k"] = jnp.zeros((Lc, B, max_len, nkv, hd), dt)
        cache["v"] = jnp.zeros((Lc, B, max_len, nkv, hd), dt)
        cache["ck"] = jnp.zeros((Lc, B, cfg.encoder_seq, nkv, hd), dt)
        cache["cv"] = jnp.zeros((Lc, B, cfg.encoder_seq, nkv, hd), dt)
    return cache


# ------------------------------------------------------------- decode ------


def decode_step(cfg: ModelConfig, params, token, cache, *, constrain=None,
                attn_impl=None, unroll=False, count_held=False):
    """One decode step. token: (B, 1) int32. Returns (logits (B,1,V), cache).
    With `count_held`, a model with expert layers also returns
    cache["moe_held"] (B, 1) int32: each token's top-k picks that fell on
    the held experts, summed over the expert layers."""
    constrain = constrain or L.no_constrain
    p = _cast(params, cfg.dtype)
    pos = cache["pos"]
    x = jnp.take(p["embed"], token, axis=0)
    x = constrain(x, "hidden")
    fam = cfg.family
    new_cache = dict(cache)

    def attn_block(lp, h, kc, vc, lora=None, cross_kv=None):
        hh = L.norm_apply(cfg, lp["attn_norm"], h)
        a, (kc, vc) = L.attn_decode_apply(cfg, lp["attn"], hh, pos=pos, k_cache=kc,
                                          v_cache=vc, lora=lora, attn_impl=attn_impl,
                                          constrain=constrain)
        h = h + a
        if cross_kv is not None:
            hh = L.norm_apply(cfg, lp["cross_norm"], h)
            a, _ = L.attn_decode_apply(cfg, lp["cross_attn"], hh, pos=pos,
                                       k_cache=cross_kv[0], v_cache=cross_kv[1],
                                       cross=True, attn_impl=attn_impl)
            h = h + a
        h, held = _ffn(cfg, lp, h, constrain)
        return h, kc, vc, held

    scan = lambda f, init, xs: lax.scan(f, init, xs, unroll=unroll)
    held = None
    if fam in ("dense", "vlm", "moe"):
        if cfg.use_mla:
            def layer(lp, h, ckv, kr):
                hh = L.norm_apply(cfg, lp["attn_norm"], h)
                a, (ckv, kr) = L.mla_decode_apply(cfg, lp["attn"], hh, pos=pos,
                                                  ckv_cache=ckv, krope_cache=kr)
                h, held = _ffn(cfg, lp, h + a, constrain)
                return h, ckv, kr, held
        else:
            layer = attn_block
        x, held = _trunk(cfg, p, x, cache, new_cache, layer, scan)
    elif fam == "ssm":
        def body(h, xs):
            lp, conv, st = xs
            hh = L.norm_apply(cfg, lp["norm"], h)
            y, conv, st = S.mamba1_decode(cfg, lp["mamba"], hh, conv_state=conv, ssm_state=st)
            return h + y, (conv, st)
        x, (conv, st) = scan(body, x, (p["layers"], cache["conv"], cache["ssm"]))
        new_cache["conv"], new_cache["ssm"] = conv, st
    elif fam == "hybrid":
        n_app = cfg.num_layers // cfg.attn_every
        stacked = jax.tree.map(
            lambda a: a.reshape((n_app, cfg.attn_every) + a.shape[1:]), p["layers"])
        conv_r = cache["conv"].reshape((n_app, cfg.attn_every) + cache["conv"].shape[1:])
        ssm_r = cache["ssm"].reshape((n_app, cfg.attn_every) + cache["ssm"].shape[1:])

        def super_body(h, xs):
            i, mstack, lora_i, kc, vc, conv_i, ssm_i = xs
            shared = jax.tree.map(lambda a: a[i % cfg.n_shared_attn_blocks], p["shared_blocks"])
            h, kc, vc, _ = attn_block(shared, h, kc, vc, lora=lora_i)

            def mamba_body(hh, ys):
                lp, conv, st = ys
                hn = L.norm_apply(cfg, lp["norm"], hh)
                y, conv, st = S.mamba2_decode(cfg, lp["mamba"], hn, conv_state=conv, ssm_state=st)
                return hh + y, (conv, st)
            h, (conv_i, ssm_i) = scan(mamba_body, h, (mstack, conv_i, ssm_i))
            return h, (kc, vc, conv_i, ssm_i)

        x, (k, v, conv, st) = scan(
            super_body, x,
            (jnp.arange(n_app), stacked, p["lora"], cache["k"], cache["v"], conv_r, ssm_r))
        new_cache["k"], new_cache["v"] = k, v
        new_cache["conv"] = conv.reshape(cache["conv"].shape)
        new_cache["ssm"] = st.reshape(cache["ssm"].shape)
    elif fam == "encdec":
        posv = jnp.asarray(pos)
        if posv.ndim == 0:
            x = x + lax.dynamic_slice_in_dim(p["dec_pos"], pos, 1, axis=0)[None]
        else:
            x = x + jnp.take(p["dec_pos"], posv, axis=0)[:, None, :]

        def body(h, xs):
            lp, kc, vc, ck, cv = xs
            h, kc, vc, _ = attn_block(lp, h, kc, vc, cross_kv=(ck, cv))
            return h, (kc, vc)
        x, (k, v) = scan(body, x, (p["dec_layers"], cache["k"], cache["v"],
                                       cache["ck"], cache["cv"]))
        new_cache["k"], new_cache["v"] = k, v

    x = L.norm_apply(cfg, p["final_norm"], x)
    head = p["embed"].T if cfg.tie_embeddings else p["lm_head"]
    logits = constrain(_logits(cfg, x, head), "logits")
    new_cache["pos"] = pos + 1
    if count_held and held is not None:
        new_cache["moe_held"] = held
    return logits, new_cache


# ------------------------------------------------------- chunked prefill ---


def prefill_chunk(cfg: ModelConfig, params, batch, cache, length=None, *,
                  constrain=None, unroll=False, count_held=False):
    """Advance a decode cache through a C-token prompt chunk.

    The chunk's tokens sit at positions [pos, pos+C) where `pos = cache["pos"]`
    (a scalar — chunked prefill is per-sequence); attention K/V is written at
    those positions and queries attend everything up to their own position,
    so feeding a prompt through successive chunks is exact for every family
    (SSM/conv state advances through the same recurrence decode uses, with
    the carried conv window prepended). `pos` may be traced: one jit
    signature per chunk *length* serves every offset.

    `length` (optional, traced): true token count when the chunk is
    right-padded to a fixed shape — with it, every chunk of a prompt reuses
    one jit signature. Padded positions write garbage K/V past the true end,
    which is harmless: later chunks/decode overwrite those positions before
    any query is allowed to attend them (position-gated masks), logits are
    taken at the last real position, and SSM/conv state is frozen past
    `length` (dt=0, conv tail sliced at the real boundary).

    batch: {"tokens": (B, C)}; vlm may add "image_embeds" on the first chunk
    (image tokens are prepended, count toward the cache position, and are
    always real — `length` counts text tokens only); encdec requires
    cache["ck"]/["cv"] already populated (see `encode_cross_kv`).
    Returns (last-position logits (B, 1, V), new cache). `count_held`
    adds cache["moe_held"] (B, C) as in `decode_step`, padding included.
    """
    constrain = constrain or L.no_constrain
    p = _cast(params, cfg.dtype)
    pos = cache["pos"]
    tokens = batch["tokens"]
    x = jnp.take(p["embed"], tokens, axis=0)
    n_img = 0
    if cfg.family == "vlm" and "image_embeds" in batch:
        img = batch["image_embeds"].astype(cfg.dtype)
        img = jax.nn.gelu(img @ p["mm_proj"]["w1"]) @ p["mm_proj"]["w2"]
        x = jnp.concatenate([img, x], axis=1)
        n_img = img.shape[1]
    C = x.shape[1]
    x = constrain(x, "hidden")
    start = pos
    fam = cfg.family
    new_cache = dict(cache)
    scan = lambda f, init, xs: lax.scan(f, init, xs, unroll=unroll)

    def attn_block(lp, h, kc, vc, lora=None, cross_kv=None):
        hh = L.norm_apply(cfg, lp["attn_norm"], h)
        a, (kc, vc) = L.attn_chunk_apply(cfg, lp["attn"], hh, start=start,
                                         k_cache=kc, v_cache=vc, lora=lora,
                                         constrain=constrain)
        h = h + a
        if cross_kv is not None:
            hh = L.norm_apply(cfg, lp["cross_norm"], h)
            a, _ = L.attn_chunk_apply(cfg, lp["cross_attn"], hh, start=start,
                                      k_cache=cross_kv[0], v_cache=cross_kv[1],
                                      cross=True)
            h = h + a
        h, held = _ffn(cfg, lp, h, constrain)
        return h, kc, vc, held

    held = None
    if fam in ("dense", "vlm", "moe"):
        if cfg.use_mla:
            def layer(lp, h, ckv, kr):
                hh = L.norm_apply(cfg, lp["attn_norm"], h)
                a, (ckv, kr) = L.mla_chunk_apply(cfg, lp["attn"], hh,
                                                 start=start, ckv_cache=ckv,
                                                 krope_cache=kr)
                h, held = _ffn(cfg, lp, h + a, constrain)
                return h, ckv, kr, held
        else:
            layer = attn_block
        x, held = _trunk(cfg, p, x, cache, new_cache, layer, scan)
    elif fam == "ssm":
        def body(h, xs):
            lp, conv, st = xs
            hh = L.norm_apply(cfg, lp["norm"], h)
            y, conv, st = S.mamba1_chunk(cfg, lp["mamba"], hh,
                                         conv_state=conv, ssm_state=st,
                                         length=length)
            return h + y, (conv, st)
        x, (conv, st) = scan(body, x, (p["layers"], cache["conv"], cache["ssm"]))
        new_cache["conv"], new_cache["ssm"] = conv.astype(cache["conv"].dtype), st
    elif fam == "hybrid":
        n_app = cfg.num_layers // cfg.attn_every
        stacked = jax.tree.map(
            lambda a: a.reshape((n_app, cfg.attn_every) + a.shape[1:]), p["layers"])
        conv_r = cache["conv"].reshape((n_app, cfg.attn_every) + cache["conv"].shape[1:])
        ssm_r = cache["ssm"].reshape((n_app, cfg.attn_every) + cache["ssm"].shape[1:])

        def super_body(h, xs):
            i, mstack, lora_i, kc, vc, conv_i, ssm_i = xs
            shared = jax.tree.map(lambda a: a[i % cfg.n_shared_attn_blocks], p["shared_blocks"])
            h, kc, vc, _ = attn_block(shared, h, kc, vc, lora=lora_i)

            def mamba_body(hh, ys):
                lp, conv, st = ys
                hn = L.norm_apply(cfg, lp["norm"], hh)
                y, conv, st = S.mamba2_chunk(cfg, lp["mamba"], hn,
                                             conv_state=conv, ssm_state=st,
                                             length=length)
                return hh + y, (conv, st)
            h, (conv_i, ssm_i) = scan(mamba_body, h, (mstack, conv_i, ssm_i))
            return h, (kc, vc, conv_i, ssm_i)

        x, (k, v, conv, st) = scan(
            super_body, x,
            (jnp.arange(n_app), stacked, p["lora"], cache["k"], cache["v"], conv_r, ssm_r))
        new_cache["k"], new_cache["v"] = k, v
        new_cache["conv"] = conv.reshape(cache["conv"].shape).astype(cache["conv"].dtype)
        new_cache["ssm"] = st.reshape(cache["ssm"].shape)
    elif fam == "encdec":
        # clipped take, not dynamic_slice: a padded chunk near the position
        # limit must never shift the real tokens' embeddings
        posv = jnp.clip(start + jnp.arange(C), 0, p["dec_pos"].shape[0] - 1)
        x = x + jnp.take(p["dec_pos"], posv, axis=0)[None]

        def body(h, xs):
            lp, kc, vc, ck, cv = xs
            h, kc, vc, _ = attn_block(lp, h, kc, vc, cross_kv=(ck, cv))
            return h, (kc, vc)
        x, (k, v) = scan(body, x, (p["dec_layers"], cache["k"], cache["v"],
                                   cache["ck"], cache["cv"]))
        new_cache["k"], new_cache["v"] = k, v

    if length is None:
        x_last, adv = x[:, -1:], C
    else:
        x_last = lax.dynamic_slice_in_dim(x, n_img + length - 1, 1, axis=1)
        adv = n_img + length
    x = L.norm_apply(cfg, p["final_norm"], x_last)
    head = p["embed"].T if cfg.tie_embeddings else p["lm_head"]
    logits = constrain(_logits(cfg, x, head), "logits")
    new_cache["pos"] = pos + adv
    if count_held and held is not None:
        new_cache["moe_held"] = held
    return logits, new_cache


def verify_chunk(cfg: ModelConfig, params, batch, cache, *, constrain=None,
                 unroll=False, count_held=False):
    """Speculative-decoding verification forward (DESIGN.md §14).

    Advances a decode cache through the C candidate tokens of a draft/verify
    round — the pending token plus the drafted continuation — and, unlike
    `prefill_chunk`, returns the logits at *every* position (the acceptance
    test needs the greedy target after each candidate) plus per-position
    state checkpoints so a rejected suffix can be rolled back exactly:

      attention KV  — written in place at [pos, pos+C); rollback is position
                      truncation (decode masks are pos-gated) plus the
                      engine's page scrub, so no checkpoint is needed;
      SSM/conv      — recurrent state cannot be truncated, so `ckpts` carries
                      "ssm" (layer_axis, B, C, ...): the scan state after
                      each position, and "conv" (layer_axis, B, K-1+C, ...):
                      the raw pre-conv input history including the carried
                      window — the state after keeping j tokens is
                      ckpts["ssm"][:, :, j-1] / ckpts["conv"][:, :, j:j+K-1].

    `cache["pos"]` may be a scalar or a per-row (B,) vector: the serving
    engine verifies all live slots in ONE batched forward, each row's chunk
    at its own decode position. Returns (logits (B, C, V), new_cache, ckpts).
    Rows are independent; callers discard rows/suffixes they reject.
    `count_held` adds new_cache["moe_held"] (B, C) as in `decode_step`.
    """
    constrain = constrain or L.no_constrain
    p = _cast(params, cfg.dtype)
    pos = cache["pos"]
    tokens = batch["tokens"]
    x = jnp.take(p["embed"], tokens, axis=0)
    B, C = tokens.shape
    x = constrain(x, "hidden")
    start = pos
    fam = cfg.family
    new_cache = dict(cache)
    ckpts = {}
    scan = lambda f, init, xs: lax.scan(f, init, xs, unroll=unroll)

    def attn_block(lp, h, kc, vc, lora=None, cross_kv=None):
        hh = L.norm_apply(cfg, lp["attn_norm"], h)
        a, (kc, vc) = L.attn_chunk_apply(cfg, lp["attn"], hh, start=start,
                                         k_cache=kc, v_cache=vc, lora=lora,
                                         constrain=constrain)
        h = h + a
        if cross_kv is not None:
            hh = L.norm_apply(cfg, lp["cross_norm"], h)
            a, _ = L.attn_chunk_apply(cfg, lp["cross_attn"], hh, start=start,
                                      k_cache=cross_kv[0], v_cache=cross_kv[1],
                                      cross=True)
            h = h + a
        h, held = _ffn(cfg, lp, h, constrain)
        return h, kc, vc, held

    held = None
    if fam in ("dense", "vlm", "moe"):
        if cfg.use_mla:
            def layer(lp, h, ckv, kr):
                hh = L.norm_apply(cfg, lp["attn_norm"], h)
                a, (ckv, kr) = L.mla_chunk_apply(cfg, lp["attn"], hh,
                                                 start=start, ckv_cache=ckv,
                                                 krope_cache=kr)
                h, held = _ffn(cfg, lp, h + a, constrain)
                return h, ckv, kr, held
        else:
            layer = attn_block
        x, held = _trunk(cfg, p, x, cache, new_cache, layer, scan)
    elif fam == "ssm":
        def body(h, xs):
            lp, conv, st = xs
            hh = L.norm_apply(cfg, lp["norm"], h)
            y, hist, hs = S.mamba1_chunk_states(cfg, lp["mamba"], hh,
                                                conv_state=conv, ssm_state=st)
            return h + y, (hist, hs)
        x, (hist, hs) = scan(body, x, (p["layers"], cache["conv"], cache["ssm"]))
        new_cache["conv"] = hist[:, :, C:].astype(cache["conv"].dtype)
        new_cache["ssm"] = hs[:, :, -1]
        ckpts = {"conv": hist, "ssm": hs}
    elif fam == "hybrid":
        n_app = cfg.num_layers // cfg.attn_every
        stacked = jax.tree.map(
            lambda a: a.reshape((n_app, cfg.attn_every) + a.shape[1:]), p["layers"])
        conv_r = cache["conv"].reshape((n_app, cfg.attn_every) + cache["conv"].shape[1:])
        ssm_r = cache["ssm"].reshape((n_app, cfg.attn_every) + cache["ssm"].shape[1:])

        def super_body(h, xs):
            i, mstack, lora_i, kc, vc, conv_i, ssm_i = xs
            shared = jax.tree.map(lambda a: a[i % cfg.n_shared_attn_blocks], p["shared_blocks"])
            h, kc, vc, _ = attn_block(shared, h, kc, vc, lora=lora_i)

            def mamba_body(hh, ys):
                lp, conv, st = ys
                hn = L.norm_apply(cfg, lp["norm"], hh)
                y, hist, hst = S.mamba2_chunk_states(cfg, lp["mamba"], hn,
                                                     conv_state=conv,
                                                     ssm_state=st)
                return hh + y, (hist, hst)
            h, (hist_i, hs_i) = scan(mamba_body, h, (mstack, conv_i, ssm_i))
            return h, (kc, vc, hist_i, hs_i)

        x, (k, v, hist, hs) = scan(
            super_body, x,
            (jnp.arange(n_app), stacked, p["lora"], cache["k"], cache["v"],
             conv_r, ssm_r))
        new_cache["k"], new_cache["v"] = k, v
        hist = hist.reshape((cfg.num_layers,) + hist.shape[2:])
        hs = hs.reshape((cfg.num_layers,) + hs.shape[2:])
        new_cache["conv"] = hist[:, :, C:].astype(cache["conv"].dtype)
        new_cache["ssm"] = hs[:, :, -1]
        ckpts = {"conv": hist, "ssm": hs}
    elif fam == "encdec":
        posv = jnp.clip(L.chunk_positions(start, B, C), 0,
                        p["dec_pos"].shape[0] - 1)
        x = x + jnp.take(p["dec_pos"], posv, axis=0)

        def body(h, xs):
            lp, kc, vc, ck, cv = xs
            h, kc, vc, _ = attn_block(lp, h, kc, vc, cross_kv=(ck, cv))
            return h, (kc, vc)
        x, (k, v) = scan(body, x, (p["dec_layers"], cache["k"], cache["v"],
                                   cache["ck"], cache["cv"]))
        new_cache["k"], new_cache["v"] = k, v

    x = L.norm_apply(cfg, p["final_norm"], x)
    head = p["embed"].T if cfg.tie_embeddings else p["lm_head"]
    logits = constrain(_logits(cfg, x, head), "logits")
    new_cache["pos"] = pos + C
    if count_held and held is not None:
        new_cache["moe_held"] = held
    return logits, new_cache, ckpts


def encode_cross_kv(cfg: ModelConfig, params, frames, *, constrain=None,
                    unroll=False):
    """Run the encoder once and project per-decoder-layer cross K/V —
    the encdec prerequisite for `prefill_chunk` (full `prefill` computes
    these inside the decoder blocks). Returns (ck, cv), each
    (num_layers, B, encoder_seq, n_kv_heads, head_dim)."""
    constrain = constrain or L.no_constrain
    p = _cast(params, cfg.dtype)
    enc_out = _encoder(cfg, p, frames.astype(cfg.dtype), constrain,
                       remat=False, unroll=unroll)

    def body(_, lp):
        ck = jnp.einsum("bsd,dhe->bshe", enc_out, lp["cross_attn"]["wk"])
        cv = jnp.einsum("bsd,dhe->bshe", enc_out, lp["cross_attn"]["wv"])
        return None, (ck, cv)
    _, (ck, cv) = lax.scan(body, None, p["dec_layers"], unroll=unroll)
    return ck, cv


# ------------------------------------------------------------- prefill -----


def prefill(cfg: ModelConfig, params, batch, max_len: int, length=None, *,
            constrain=None, remat=False, unroll=False):
    """Process the prompt, fill the cache, return last-position logits.

    Implemented as forward + KV collection for attention archs; for SSM archs
    the scan's final state is the cache.

    `length` (optional, traced): true token count when `batch["tokens"]` is
    right-padded to a bucketed shape — one jit signature then serves every
    prompt length in the bucket. Exactness is preserved: logits are taken at
    the last *real* position, `cache["pos"]` gates attention so padded K/V
    is never attended, and SSM/conv state is frozen past `length` (padded
    positions get dt=0, the conv tail is sliced at the real boundary).
    """
    constrain = constrain or L.no_constrain
    p = _cast(params, cfg.dtype)
    tokens = batch["tokens"]
    B = tokens.shape[0]
    cache = init_decode_cache(cfg, B, max_len)
    x = jnp.take(p["embed"], tokens, axis=0)
    n_img = 0
    if cfg.family == "vlm":
        img = batch["image_embeds"].astype(cfg.dtype)
        img = jax.nn.gelu(img @ p["mm_proj"]["w1"]) @ p["mm_proj"]["w2"]
        x = jnp.concatenate([img, x], axis=1)
        n_img = img.shape[1]
    S_in = x.shape[1]
    positions = jnp.arange(S_in)[None, :]
    x = constrain(x, "hidden")

    def pad_to_cache(arr):  # (L?, B, S, ...) -> (..., max_len, ...) on axis=2
        assert arr.shape[2] <= max_len, (
            f"prompt ({arr.shape[2]} incl. image/frame tokens) exceeds cache max_len={max_len}")
        pad = [(0, 0)] * arr.ndim
        pad[2] = (0, max_len - arr.shape[2])
        return jnp.pad(arr, pad)

    fam = cfg.family
    if fam in ("dense", "vlm"):
        x, kvs, _ = _scan_blocks(cfg, p["layers"], x, positions, constrain,
                                 moe=False, remat=remat, unroll=unroll)
        if cfg.use_mla:
            cache["ckv"] = pad_to_cache(kvs[0].astype(cache["ckv"].dtype))
            cache["krope"] = pad_to_cache(kvs[1].astype(cache["krope"].dtype))
        else:
            cache["k"] = pad_to_cache(kvs[0].astype(cache["k"].dtype))
            cache["v"] = pad_to_cache(kvs[1].astype(cache["v"].dtype))
    elif fam == "moe":
        parts_k, parts_v = [], []
        if cfg.first_dense_layers:
            x, kvs, _ = _scan_blocks(cfg, p["dense_layers"], x, positions, constrain,
                                     moe=False, remat=remat, unroll=unroll)
            parts_k.append(kvs[0]); parts_v.append(kvs[1])
        x, kvs, _ = _scan_blocks(cfg, p["layers"], x, positions, constrain,
                                 moe=True, remat=remat, unroll=unroll)
        parts_k.append(kvs[0]); parts_v.append(kvs[1])
        k = jnp.concatenate(parts_k, 0) if len(parts_k) > 1 else parts_k[0]
        v = jnp.concatenate(parts_v, 0) if len(parts_v) > 1 else parts_v[0]
        if cfg.use_mla:
            cache["ckv"] = pad_to_cache(k.astype(cache["ckv"].dtype))
            cache["krope"] = pad_to_cache(v.astype(cache["krope"].dtype))
        else:
            cache["k"] = pad_to_cache(k.astype(cache["k"].dtype))
            cache["v"] = pad_to_cache(v.astype(cache["v"].dtype))
    elif fam == "ssm":
        def body(carry, lp):
            h = carry
            hh = L.norm_apply(cfg, lp["norm"], h)
            x_in, z = S._mamba1_ssm_inputs(cfg, lp["mamba"], hh)
            xc = jax.nn.silu(S.causal_depthwise_conv(x_in, lp["mamba"]["conv_w"], lp["mamba"]["conv_b"]))
            dt, A, B_m, C_m = S._mamba1_scan_params(cfg, lp["mamba"], xc)
            if length is not None:
                # dt=0 on padded positions: decay 1, contribution 0 — the
                # recurrent state is exactly the state at `length`.
                dt = dt * (jnp.arange(S_in)[None, :, None] < length)
            y, hfin = S.mamba1_scan_ref(xc, dt, A, B_m, C_m, lp["mamba"]["D"])
            out = (y * jax.nn.silu(z)) @ lp["mamba"]["out_proj"]
            # zero left-pad so a prompt shorter than the conv window gets
            # real zero history, not a short/misaligned window
            hist = jnp.pad(x_in, ((0, 0), (cfg.ssm_conv - 1, 0), (0, 0)))
            if length is None:
                conv_tail = hist[:, S_in:, :]
            else:
                conv_tail = lax.dynamic_slice_in_dim(
                    hist, length, cfg.ssm_conv - 1, axis=1)
            return h + out, (conv_tail, hfin)
        x, (conv, st) = lax.scan(body, x, p["layers"], unroll=unroll)
        cache["conv"] = conv.astype(cache["conv"].dtype)
        cache["ssm"] = st
    elif fam == "hybrid":
        n_app = cfg.num_layers // cfg.attn_every
        stacked = jax.tree.map(
            lambda a: a.reshape((n_app, cfg.attn_every) + a.shape[1:]), p["layers"])

        def super_body(carry, xs):
            h = carry
            i, mstack, lora_i = xs
            shared = jax.tree.map(lambda a: a[i % cfg.n_shared_attn_blocks], p["shared_blocks"])
            h, kv, _ = _dense_block(cfg, shared, h, positions, constrain, lora=lora_i)

            def mamba_body(hh, lp):
                hn = L.norm_apply(cfg, lp["norm"], hh)
                zz, xbc_raw, dt_raw = S._mamba2_proj(cfg, lp["mamba"], hn)
                xbc = jax.nn.silu(S.causal_depthwise_conv(xbc_raw, lp["mamba"]["conv_w"], lp["mamba"]["conv_b"]))
                di, N = cfg.d_inner, cfg.ssm_state
                x_i, B_m, C_m = xbc[..., :di], xbc[..., di:di + N], xbc[..., di + N:]
                dt = jax.nn.softplus(dt_raw + lp["mamba"]["dt_bias"])
                if length is not None:
                    dt = dt * (jnp.arange(S_in)[None, :, None] < length)
                A = -jnp.exp(lp["mamba"]["A_log"].astype(jnp.float32))
                Bsz, S_len = x_i.shape[0], x_i.shape[1]
                y, hfin = S.mamba2_ssd_ref(
                    x_i.reshape(Bsz, S_len, cfg.n_ssm_heads, cfg.mamba_headdim),
                    dt, A, B_m, C_m, lp["mamba"]["D"], chunk=cfg.ssm_chunk)
                y = y.reshape(Bsz, S_len, di)
                y = L.rms_norm(y * jax.nn.silu(zz), lp["mamba"]["norm_w"], cfg.norm_eps)
                # raw pre-conv inputs, zero-padded history (see ssm branch)
                hist = jnp.pad(xbc_raw,
                               ((0, 0), (cfg.ssm_conv - 1, 0), (0, 0)))
                if length is None:
                    conv_tail = hist[:, S_in:, :]
                else:
                    conv_tail = lax.dynamic_slice_in_dim(
                        hist, length, cfg.ssm_conv - 1, axis=1)
                return hh + y @ lp["mamba"]["out_proj"], (conv_tail, hfin)

            h, (conv_i, ssm_i) = lax.scan(mamba_body, h, mstack, unroll=unroll)
            return h, (kv[0], kv[1], conv_i, ssm_i)

        x, (k, v, conv, st) = lax.scan(super_body, x,
                                       (jnp.arange(n_app), stacked, p["lora"]), unroll=unroll)
        cache["k"] = pad_to_cache(k.astype(cache["k"].dtype))
        cache["v"] = pad_to_cache(v.astype(cache["v"].dtype))
        cache["conv"] = conv.reshape(cache["conv"].shape).astype(cache["conv"].dtype)
        cache["ssm"] = st.reshape(cache["ssm"].shape)
    elif fam == "encdec":
        enc_out = _encoder(cfg, p, batch["frames"].astype(cfg.dtype), constrain, remat=remat, unroll=unroll)
        pos_emb = lax.dynamic_slice_in_dim(p["dec_pos"], 0, tokens.shape[1], axis=0)
        x = x + pos_emb[None]

        def body(h, lp):
            h, kv, ckv = _decoder_block(cfg, lp, h, positions, enc_out, constrain)
            return h, (kv, ckv)
        x, (kvs, ckvs) = lax.scan(body, x, p["dec_layers"], unroll=unroll)
        cache["k"] = pad_to_cache(kvs[0].astype(cache["k"].dtype))
        cache["v"] = pad_to_cache(kvs[1].astype(cache["v"].dtype))
        cache["ck"] = ckvs[0].astype(cache["ck"].dtype)
        cache["cv"] = ckvs[1].astype(cache["cv"].dtype)

    if length is None:
        x_last, true_len = x[:, -1:], S_in
    else:
        x_last = lax.dynamic_slice_in_dim(x, n_img + length - 1, 1, axis=1)
        true_len = n_img + length
    x = L.norm_apply(cfg, p["final_norm"], x_last)
    head = p["embed"].T if cfg.tie_embeddings else p["lm_head"]
    logits = constrain(_logits(cfg, x, head), "logits")
    cache["pos"] = jnp.asarray(true_len, jnp.int32)
    return logits, cache
