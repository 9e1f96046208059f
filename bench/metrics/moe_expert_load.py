"""Routed token-expert pairs that a held expert computes per decode step,
on average, in the traced stretch: the engine's ``moe_pairs_held`` over
``moe_expert_steps`` (held experts x MoE layers x decode steps), counted on
the device in each fused window and fetched with its tokens. In the
expert-parallel deployment a held expert would see the pairs of every
chip's rows, so this reads the cut's main distortion."""

from bench import counts


def read(run):
    if not run.events:
        return None
    pairs = counts.total(run.events, "engine.count.moe_pairs_held")
    steps = counts.total(run.events, "engine.count.moe_expert_steps")
    return pairs / steps if pairs is not None and steps else None
