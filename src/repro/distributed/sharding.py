"""Sharding rules: FSDP+TP 2D parameter layout, activation/cache specs.

Policy (DESIGN.md §4):
  - every large matrix: "feature" dim over `model` (TP), other big dim over
    `data` (FSDP / ZeRO-3); XLA all-gathers FSDP shards per layer inside the
    scan loop (overlappable) and all-reduces TP partials.
  - axes only apply when the dim is divisible by the axis size (GQA kv=8 on
    a 16-way model axis stays replicated; qk-norm scales etc. replicate).
  - batch over (pod, data); KV caches: batch over data, *sequence over
    model* (sequence-sharded decode: GSPMD reduces the masked softmax over
    the sharded axis; the shard_map flash-decoding variant is the optimized
    path); SSM state: d_inner over model.
  - optimizer state mirrors its parameter's spec (extra leading quant-block
    dims for adam8bit replicate).

Serving (`serving=True`, DESIGN.md §15) uses a column-parallel layout
instead: the dense projections shard only dims they do not contract, over
`model`, and replicate over `data` (slot batch parallelism); the
activations around the output projections (`wo`, `w_down`) are gathered
("gather" constraint), so norms also reduce whole rows. No dot or norm
then reduces across devices, no partial sums meet in an all-reduce, and a
bf16 model decodes exactly the tokens it decodes on one device.
"""
from __future__ import annotations

from functools import partial

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig
from repro.launch.mesh import batch_axes

FSDP = "data"
TP = "model"

def shard_map(f, *, mesh, in_specs, out_specs, axis_names=None,
              check_vma=True):
    """`jax.shard_map`, manual over `axis_names` (None = every mesh axis)."""
    kw = {} if axis_names is None else {"axis_names": set(axis_names)}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma, **kw)

# trailing-dim roles per leaf name: 'f' = FSDP(data), 't' = TP(model),
# '.' = replicated. Leading dims (layer stacks etc.) always replicate.
_ROLES = {
    "embed": "tf",
    "lm_head": "ft",
    "dec_pos": "..",
    "wq": "ft.", "wk": "ft.", "wv": "ft.",
    "wo": "t.f",
    "bq": "t.", "bk": "t.", "bv": "t.",
    "q_norm": ".", "k_norm": ".",
    "w": ".", "b": ".",                      # norms
    "w_gate": "ft", "w_up": "ft", "w_in": "ft", "w_down": "tf",
    "router": "f.",
    "wq_mla": "ft.",
    "w_dkv": "f.",
    "w_uk": "ft.", "w_uv": "ft.",
    "kv_norm": ".",
    "in_proj": "ft",                          # mamba1 (aligned halves)
    "in_proj_m2": "f.",                       # mamba2 (mixed boundary)
    "conv_w": ".t", "conv_b": "t",
    "x_proj": "t.", "dt_proj": ".t", "dt_bias": "t",
    "A_log": "t.", "A_log_1d": "t", "D": "t",
    "norm_w": "t",
    "out_proj": "tf",
    "w1": "f.", "w2": "f.",                   # mm projector
    "a_q": "f.", "a_k": "f.", "a_v": "f.", "a_o": "f.",
    "b_q": ".t", "b_k": ".t", "b_v": ".t", "b_o": "..",
}

# serving overrides: column-parallel over `model` only ('t' on a dim the
# weight's matmul never contracts); the embedding shards its vocab, so the
# lookup's cross-device sum adds exact zeros
SERVING_ROLES = {
    "embed": "t.", "lm_head": ".t",
    "wq": ".t.", "wk": ".t.", "wv": ".t.", "wo": "..t",
    "w_gate": ".t", "w_up": ".t", "w_in": ".t", "w_down": ".t",
}


def _spec_for_leaf(path, leaf, mesh, cfg: ModelConfig, overrides=None) -> P:
    name = None
    for p in reversed(path):
        if hasattr(p, "key"):
            name = p.key
            break
    roles = (overrides or {}).get(name, _ROLES.get(name))
    # disambiguate shared names
    if name == "in_proj" and cfg.mamba_version == 2:
        roles = _ROLES["in_proj_m2"]
    if name == "A_log" and getattr(leaf, "ndim", 0) >= 1 and cfg.mamba_version == 2:
        roles = None  # stacked (L, h): trailing dim h
        roles = "t"
    if roles is None:
        return P()
    shape = leaf.shape
    ndim = len(shape)
    roles = roles[-ndim:] if len(roles) > ndim else roles
    lead = ndim - len(roles)
    spec = [None] * lead
    msizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    for dim, role in zip(shape[lead:], roles):
        if role == "f" and FSDP in msizes and dim % msizes[FSDP] == 0 and dim >= msizes[FSDP]:
            spec.append(FSDP)
        elif role == "t" and TP in msizes and dim % msizes[TP] == 0 and dim >= msizes[TP]:
            spec.append(TP)
        else:
            spec.append(None)
    return P(*spec)


def param_specs(cfg: ModelConfig, params_shape, mesh, overrides=None):
    """PartitionSpec pytree mirroring an (abstract) param pytree.

    `overrides`: {leaf_name: role_string} — variant sharding layouts (e.g.
    expert parallelism: w_gate -> "tf." shards experts over `model`)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_shape)
    specs = [_spec_for_leaf(path, leaf, mesh, cfg, overrides) for path, leaf in flat]
    return jax.tree_util.tree_unflatten(treedef, specs)


def param_shardings(cfg: ModelConfig, params_shape, mesh, *,
                    serving: bool = False):
    specs = param_specs(cfg, params_shape, mesh,
                        SERVING_ROLES if serving else None)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs)


def opt_state_specs(cfg: ModelConfig, opt_shape, pspecs, mesh):
    """Optimizer-state specs: mirror the param spec where shapes match
    (adam m/v); 8-bit Adam quant blocks shard their block dim over
    (data, model); factored stats and scalars replicate."""
    import jax.tree_util as jtu

    pflat = {jtu.keystr(path): spec
             for path, spec in jtu.tree_flatten_with_path(pspecs)[0]}
    total = 1
    for a in ("data", "model"):
        if a in mesh.axis_names:
            total *= mesh.shape[a]

    def parent_param_spec(path):
        s = jtu.keystr(path[:-1])            # drop the mq/ms/m/v component
        for pkey, pspec in pflat.items():
            if s.endswith(pkey):
                return pspec
        return None

    def spec_of(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else ""
        if name in ("mq", "vq", "ms", "vs", "m", "v"):
            pspec = parent_param_spec(path)
            if pspec is not None and len(pspec) == leaf.ndim:
                if name in ("ms", "vs"):
                    # scales: last axis shrank by q_block; keep axis only if
                    # still divisible
                    last = pspec[-1]
                    msz = mesh.shape[last] if last else 1
                    ok = last is not None and leaf.shape[-1] % msz == 0
                    return P(*pspec[:-1], last if ok else None)
                return pspec
        s = jtu.keystr(path)
        for pkey, pspec in pflat.items():
            if s.endswith(pkey):
                if len(pspec) == getattr(leaf, "ndim", 0):
                    return pspec
        return P()

    flat, treedef = jtu.tree_flatten_with_path(opt_shape)
    return jtu.tree_unflatten(treedef, [spec_of(p, l) for p, l in flat])


# ------------------------------------------------------- activations -------


def batch_spec(mesh, batch_size: int) -> tuple:
    """Largest prefix of (pod, data) that divides the batch."""
    axes = []
    n = 1
    for a in batch_axes(mesh):
        sz = mesh.shape[a]
        if batch_size % (n * sz) == 0:
            axes.append(a)
            n *= sz
    return tuple(axes) if axes else ()


def make_constrain(mesh, batch_size: int, *, ep_moe: bool = False,
                   serving: bool = False):
    """Activation sharding hook threaded through model forward/decode.

    ep_moe: pin MoE dispatch/combine buffers (E, C, d) to P(data, None, None)
    — experts live on data shards, so GSPMD moves *tokens* (all-to-all)
    instead of all-gathering index tensors and reducing dispatch products.
    serving: gather the activations around the output projections
    ("gather") for the column-parallel serving layout; a no-op otherwise."""
    baxes = batch_spec(mesh, batch_size)
    b = baxes if baxes else None

    def constrain(x, kind):
        if kind == "hidden" or (kind == "gather" and serving):
            spec = P(b, *([None] * (x.ndim - 1)))
        elif kind == "logits":
            spec = P(b, *([None] * (x.ndim - 2)), TP)
        elif kind == "moe_dispatch" and ep_moe:
            e_ax = FSDP if x.shape[0] % mesh.shape[FSDP] == 0 else None
            spec = P(e_ax, *([None] * (x.ndim - 1)))
        elif kind == "moe_grouped":
            g_ax = FSDP if x.shape[0] % mesh.shape[FSDP] == 0 else None
            spec = P(g_ax, *([None] * (x.ndim - 1)))
        else:
            return x
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    return constrain


def input_sharding(mesh, batch_size: int, ndim: int):
    baxes = batch_spec(mesh, batch_size)
    b = baxes if baxes else None
    return NamedSharding(mesh, P(b, *([None] * (ndim - 1))))


def cache_specs(cfg: ModelConfig, cache_shape, mesh, batch_size: int):
    """Decode-cache specs: batch over data, sequence over model (for KV),
    d_inner over model (for SSM state)."""
    baxes = batch_spec(mesh, batch_size)
    b = baxes if baxes else None
    msz = mesh.shape[TP] if TP in mesh.axis_names else 1

    def spec(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else ""
        shp = leaf.shape
        if name in ("k", "v"):          # (L, B, S, Hkv, hd)
            s = TP if shp[2] % msz == 0 else None
            return P(None, b, s, None, None)
        if name in ("ck", "cv"):        # (L, B, enc_S, Hkv, hd)
            s = TP if shp[3] % msz == 0 else None
            return P(None, b, None, s, None)
        if name in ("ckv", "krope"):    # (L, B, S, r)
            s = TP if shp[2] % msz == 0 else None
            return P(None, b, s, None)
        if name == "ssm":               # (L, B, di, N) or (L, B, h, p, N)
            if len(shp) == 4:
                s = TP if shp[2] % msz == 0 else None
                return P(None, b, s, None)
            s = TP if shp[2] % msz == 0 else None
            return P(None, b, s, None, None)
        if name == "conv":              # (L, B, K-1, C)
            s = TP if shp[3] % msz == 0 else None
            return P(None, b, None, s)
        return P()

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_shape)
    return jax.tree_util.tree_unflatten(
        treedef, [spec(p, l) for p, l in flat])


def pool_specs(pools: dict, mesh) -> dict:
    """Paged-KV pool specs (DESIGN.md §15): page tables are host-local
    integers, so the page axis (axis 1) always replicates — a page id must
    dereference the same physical page on every device. The per-position
    feature axes shard over `model` where divisible: attention heads for
    k/v, the latent/rope rank for MLA's ckv/krope. Shapes are
    (layer_axis, num_pages, page_size, *tail)."""
    msz = mesh.shape[TP] if TP in mesh.axis_names else 1
    specs = {}
    for name, a in pools.items():
        tail = a.shape[3:]
        spec = [None, None, None]
        for i, dim in enumerate(tail):
            # shard the first tail dim that divides (heads for k/v, rank
            # for ckv/krope); everything after it replicates
            if i == 0 and dim % msz == 0 and dim >= msz:
                spec.append(TP)
            else:
                spec.append(None)
        specs[name] = P(*spec)
    return specs


def to_shardings(mesh, specs):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))
