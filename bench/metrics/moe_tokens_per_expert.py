"""Expert layer: tokens a held expert serves per target pass in the window,
the window differences of the scheduler's ``moe_expert_tokens`` (top-k
assignments that land on a held expert, over every expert layer of every
target pass: verify and prefill) over ``moe_expert_slots`` (held experts ×
expert layers × target passes). Draft passes are not counted, though each
reads every held expert's weights as well: a speculative cycle reads them
γ + 1 times, and this is the share of one of those reads, the verify's.
None where the program counts no expert layer."""


def read(r):
    def diff(name):
        return r.counters1.get(name, 0) - r.counters0.get(name, 0)

    slots = diff("moe_expert_slots")
    return diff("moe_expert_tokens") / slots if slots else None
