"""moe.tokens_per_expert.batch: picks that reached a held expert per held
expert per MoE layer per decode dispatch, from the program's on-device
expert counter (``run()``'s ``expert_load``).  A deployment of 8-way expert
parallelism at 32 slots a chip gives 24."""
from bench import layer_work


def read(ctx):
    load = layer_work.expert_load(ctx)
    if load is None or not load["decode"][1]:
        return None
    picks, slots, _ = load["decode"]
    return picks / slots
