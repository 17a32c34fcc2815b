"""Expert layer (`models/layers.py` `moe_apply`): the token-to-expert
assignments that fell on the experts this chip holds (the engine's
`held_assignments`: real tokens only, summed over the expert layers), per
held expert, per expert layer and per phase-program call (prefill chunk or
verify round) of the traced wave: how many tokens each held expert serves
for each read of its weights. Moves queries_per_min."""


def read(r):
    held = r.engine.get("held_assignments")
    calls = r.engine["prefill_chunks"] + r.engine["decode_steps"]
    c = r.conf
    if held is None or not calls or "n_routed_experts" not in c:
        return None
    layers = c["num_hidden_layers"] - c["first_k_dense_replace"]
    return held / (c["n_routed_experts"] * layers * calls)
