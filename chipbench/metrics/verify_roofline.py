"""Engine phase programs, speculative verify: the least time the chip could
take for the traced wave's verify rounds over the device time of the
program that ran them (`jit_verify_round`). The least time is the larger
of the served tokens' FLOPs over the peak and, over the HBM bandwidth, the
weights read once per round plus the prompt KV each round read
(`counts.py`, the decode term of `model_roofline`), per chip as there.
Moves queries_per_min."""


def read(r):
    device = (r.trace or {}).get("programs", {}).get("jit_verify_round")
    if not device:
        return None
    c, e = r.counts, r.engine
    w = c.useful_work(r.conf, r.requests, e["prefill_tokens"])
    least = max(w["decode_flops"] / r.peaks["bf16_flops_per_s"],
                (e["decode_steps"] * c.weight_bytes_per_call(r.conf)
                 + w["decode_bytes"]) / r.peaks["hbm_bytes_per_s"])
    return 100.0 * least / r.chips / device
