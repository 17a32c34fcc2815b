"""Engine phase programs (chunked prefill, verify, decode): the least time
the chip could take for the traced wave's calls over the device time of
every program the wave ran except the retrieval ranking (`l2_rank_device`).

Per kind of call, the least time is the larger of its useful FLOPs over the
peak and its least bytes over the HBM bandwidth: every call reads the
weights once, prefill writes the KV of the tokens it fills, and each verify
round reads the prompt KV of the requests in it (`counts.py`). Summing per
kind is a floor of the per-call sum, so the share cannot pass the true one.
Per chip: on a serving mesh each of the wave's chips does 1/chips of the
FLOPs and reads 1/chips of the weights and KV, so the least time is divided
by the chips, against the device time averaged over them (collectives count
nothing, so the share stays a floor). Moves queries_per_min."""


def read(r):
    if not r.trace or not r.trace["programs"]:
        return None
    c, e = r.counts, r.engine
    w = c.useful_work(r.conf, r.requests, e["prefill_tokens"])
    per_call = c.weight_bytes_per_call(r.conf)
    flops, bw = r.peaks["bf16_flops_per_s"], r.peaks["hbm_bytes_per_s"]
    rounds = e["decode_steps"]
    least = (max(w["prefill_flops"] / flops,
                 (e["prefill_chunks"] * per_call + w["prefill_bytes"]) / bw)
             + max(w["decode_flops"] / flops,
                   (rounds * per_call + w["decode_bytes"]) / bw))
    device = sum(s for name, s in r.trace["programs"].items()
                 if "l2_rank" not in name)
    return 100.0 * least / r.chips / device if device else None
