"""Operations and bytes of the dense decoder's calls, from a configuration's
shapes alone. A test holds the parameter counts to `jax.eval_shape` of the
program's `init_params`.

Conventions: a multiply-add is 2 FLOPs; attention costs 4 * heads *
head_dim FLOPs per layer for each (query position, attended position) pair
(scores and the weighted sum of values); norms, rotary embedding, softmax
and biases are left out (each is under 0.1% of a token's FLOPs here), so
the counts are a floor of the work.
"""
from __future__ import annotations

BF16 = 2


def layer_matmul_params(conf: dict) -> int:
    d, nq, nkv = (conf["hidden_size"], conf["num_attention_heads"],
                  conf["num_key_value_heads"])
    hd, ff = conf["head_dim"], conf["intermediate_size"]
    return d * (nq + 2 * nkv) * hd + nq * hd * d + 3 * d * ff


def layer_other_params(conf: dict) -> int:
    """Norm weights and biases of one layer."""
    d, nq, nkv, hd = (conf["hidden_size"], conf["num_attention_heads"],
                      conf["num_key_value_heads"], conf["head_dim"])
    n = 2 * d
    if conf["attention_bias"]:
        n += (nq + 2 * nkv) * hd
    if conf["qk_norm"]:
        n += 2 * hd
    return n


def trunk_matmul_params(conf: dict) -> int:
    return conf["num_hidden_layers"] * layer_matmul_params(conf)


def head_params(conf: dict) -> int:
    return conf["hidden_size"] * conf["vocab_size"]


def total_params(conf: dict) -> int:
    L, d, V = conf["num_hidden_layers"], conf["hidden_size"], conf["vocab_size"]
    emb = V * d * (1 if conf["tie_word_embeddings"] else 2)
    return emb + d + L * (layer_matmul_params(conf) + layer_other_params(conf))


def weight_bytes_per_call(conf: dict) -> int:
    """Bytes every forward call must read at least once: the layers and the
    LM head (the embedding table is gathered a few rows at a time, so an
    untied table counts nothing)."""
    L, d = conf["num_hidden_layers"], conf["hidden_size"]
    per_layer = layer_matmul_params(conf) + layer_other_params(conf)
    return BF16 * (L * per_layer + head_params(conf) + d)


def kv_bytes_per_token(conf: dict) -> int:
    return (conf["num_hidden_layers"] * 2 * conf["num_key_value_heads"]
            * conf["head_dim"] * BF16)


def attn_flops_per_pair(conf: dict) -> int:
    return (conf["num_hidden_layers"] * 4 * conf["num_attention_heads"]
            * conf["head_dim"])


def useful_work(conf: dict, requests: list, prefill_tokens: int) -> dict:
    """Floors of the FLOPs and bytes of the work done for `requests`, a list
    of (prompt length, shared prefix length, served tokens, accepted draft
    tokens), where `prefill_tokens` prompt tokens were prefilled (after
    prefix-cache hits).

    Prefill: every prefilled token passes the trunk; the LM head runs once
    per request, at its last prompt position; attention is counted for the
    positions after the shared prefix only, each attending itself and what
    precedes it (a prefix the cache served costs nothing). Decode: every
    served token but the last is fed back through the trunk and the head
    (rejected draft positions count nothing) and attends the whole context
    before it. Bytes: weights per call are added by the caller; here the KV
    written by prefill and, per verify round a request took part in, its
    prompt's KV read once."""
    trunk, head = trunk_matmul_params(conf), head_params(conf)
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
