"""Expert layer (`models/layers.py` `moe_apply`): the share of the traced
wave's token-to-expert assignments that fell on the experts this chip
holds (the engine's `held_assignments` over `routed_assignments`); 25% for
a quarter of the experts under even routing. Moves queries_per_min."""


def read(r):
    held = r.engine.get("held_assignments")
    routed = r.engine.get("routed_assignments")
    if held is None or not routed:
        return None
    return 100.0 * held / routed
