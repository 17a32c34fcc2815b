"""Model step (`models/`): the traced wave's useful model FLOPs over the
wave's length in the profiler's trace times the chip's peak bf16 FLOP/s.
Useful FLOPs (`counts.py`) are the prompt tokens prefilled after prefix
hits and the tokens served, 2 FLOPs per matmul parameter and token, the LM
head only where logits are taken, attention over the positions each
attends; rejected draft positions count nothing. Moves queries_per_min."""


def read(r):
    w = r.counts.useful_work(r.conf, r.requests, r.engine["prefill_tokens"])
    flops = w["prefill_flops"] + w["decode_flops"]
    if not flops or not r.trace or not r.trace["busy_s"]:
        return None
    return 100.0 * flops / (r.trace["window_s"] * r.chips
                            * r.peaks["bf16_flops_per_s"])
