"""Operations and bytes of the calls of DeepSeek-V2's decoder (latent
attention and the expert layer), as one chip of an expert-parallel
deployment runs it, from a configuration's shapes alone. A test holds the
parameter count to `jax.eval_shape` of `mla_moe_reference.make_weights`.

Conventions, as `counts.py`'s: a multiply-add is 2 FLOPs; norms, rotary
embedding, softmax and the gate's top-k are left out, so the counts are a
floor of the work. Attention is counted in the absorbed form the program
runs (the K/V up-projections folded into the query and context sides): per
layer and per (query position, attended position) pair, the scores over
the cached latent and rotary key (`kv_lora_rank + qk_rope_head_dim` per
head) and the context over the latent (`kv_lora_rank` per head). Routed
experts count at their expectation: each token picks `num_experts_per_tok`
of the published experts, of which the held share `n_routed_experts /
published_n_routed_experts` falls on this chip. The program computes every
held expert on every token (weighted by its gate), which is more work than
this floor.
"""
from __future__ import annotations

BF16 = 2


def _moe_layers(conf: dict) -> int:
    return conf["num_hidden_layers"] - conf["first_k_dense_replace"]


def attn_params(conf: dict) -> int:
    """Matrix parameters of one latent-attention layer: q, the compressed
    KV and rotary key, the K and V up-projections, the output."""
    d, H = conf["hidden_size"], conf["num_attention_heads"]
    r, qn, qr, vd = (conf["kv_lora_rank"], conf["qk_nope_head_dim"],
                     conf["qk_rope_head_dim"], conf["v_head_dim"])
    return d * H * (qn + qr) + d * (r + qr) + r * H * (qn + vd) + H * vd * d


def norm_params(conf: dict) -> int:
    """Norm weights of one layer: the two RMSNorms and the latent's."""
    return 2 * conf["hidden_size"] + conf["kv_lora_rank"]


def expert_params(conf: dict) -> int:
    return 3 * conf["hidden_size"] * conf["moe_intermediate_size"]


def moe_params(conf: dict) -> int:
    """The expert layer's parameters on this chip: the router over every
    published expert, the held experts, the shared experts."""
    return (conf["hidden_size"] * conf["published_n_routed_experts"]
            + (conf["n_routed_experts"] + conf["n_shared_experts"])
            * expert_params(conf))


def dense_mlp_params(conf: dict) -> int:
    return 3 * conf["hidden_size"] * conf["intermediate_size"]


def head_params(conf: dict) -> int:
    return conf["hidden_size"] * conf["vocab_size"]


def trunk_params(conf: dict) -> int:
    """Every parameter of the layers (this chip's share)."""
    nd = conf["first_k_dense_replace"]
    per = attn_params(conf) + norm_params(conf)
    return (nd * (per + dense_mlp_params(conf))
            + _moe_layers(conf) * (per + moe_params(conf)))


def total_params(conf: dict) -> int:
    return trunk_params(conf) + 2 * head_params(conf) + conf["hidden_size"]


def weight_bytes_per_call(conf: dict) -> int:
    """Bytes every forward call must read at least once: the layers, the
    held experts included, and the LM head (the embedding table is gathered
    a few rows at a time and counts nothing)."""
    return BF16 * (trunk_params(conf) + head_params(conf)
                   + conf["hidden_size"])


def kv_bytes_per_token(conf: dict) -> int:
    """The latent cache: `kv_lora_rank + qk_rope_head_dim` numbers a token
    a layer."""
    return (conf["num_hidden_layers"]
            * (conf["kv_lora_rank"] + conf["qk_rope_head_dim"]) * BF16)


def attn_flops_per_pair(conf: dict) -> int:
    r, qr = conf["kv_lora_rank"], conf["qk_rope_head_dim"]
    return (conf["num_hidden_layers"] * 2 * conf["num_attention_heads"]
            * (2 * r + qr))


def token_matmul_params(conf: dict) -> float:
    """Matrix parameters a token passes through in the layers, the routed
    experts at their expectation on this chip."""
    held_share = conf["n_routed_experts"] / conf["published_n_routed_experts"]
    routed = conf["num_experts_per_tok"] * held_share * expert_params(conf)
    moe = (attn_params(conf) + conf["hidden_size"]
           * conf["published_n_routed_experts"]
           + conf["n_shared_experts"] * expert_params(conf) + routed)
    return (conf["first_k_dense_replace"]
            * (attn_params(conf) + dense_mlp_params(conf))
            + _moe_layers(conf) * moe)


def useful_work(conf: dict, requests: list, prefill_tokens: int) -> dict:
    """Floors of the FLOPs and bytes of the work done for `requests`, a list
    of (prompt length, shared prefix length, served tokens, accepted draft
    tokens), where `prefill_tokens` prompt tokens were prefilled (after
    prefix-cache hits); what each term counts is as in `counts.useful_work`."""
    trunk, head = token_matmul_params(conf), head_params(conf)
    pair = attn_flops_per_pair(conf)
    kv = kv_bytes_per_token(conf)
    pre_attn = dec_attn = dec_tokens = kv_read = 0
    for plen, shared, n_out, accepted in requests:
        pre_attn += (plen * (plen + 1) - shared * (shared + 1)) // 2
        fed = max(n_out - 1, 0)
        dec_tokens += fed
        dec_attn += fed * (plen + 1) + fed * (fed - 1) // 2
        kv_read += max(fed - accepted, 0) * plen * kv
    return {
        "prefill_flops": 2 * trunk * prefill_tokens + 2 * head * len(requests)
        + pair * pre_attn,
        "decode_flops": 2 * (trunk + head) * dec_tokens + pair * dec_attn,
        "prefill_bytes": kv * prefill_tokens,
        "decode_bytes": kv_read,
        "served_tokens": sum(r[2] for r in requests),
    }
