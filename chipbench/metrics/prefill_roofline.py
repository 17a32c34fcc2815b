"""Engine phase programs, chunked prefill: the least time the chip could
take for the traced wave's prefill chunks over the device time of the
program that ran them (`jit_prefill_chunk`). The least time is the larger
of the prefill FLOPs over the peak and, over the HBM bandwidth, the weights
read once per chunk plus the KV the chunks wrote (`counts.py`, the prefill
term of `model_roofline`), per chip as there. Moves queries_per_min."""


def read(r):
    device = (r.trace or {}).get("programs", {}).get("jit_prefill_chunk")
    if not device:
        return None
    c, e = r.counts, r.engine
    w = c.useful_work(r.conf, r.requests, e["prefill_tokens"])
    least = max(w["prefill_flops"] / r.peaks["bf16_flops_per_s"],
                (e["prefill_chunks"] * c.weight_bytes_per_call(r.conf)
                 + w["prefill_bytes"]) / r.peaks["hbm_bytes_per_s"])
    return 100.0 * least / r.chips / device
