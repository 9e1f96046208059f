"""Share (%) of KV slots that decoded in the window's decode steps:
``occupancy_sum / steps / num_slots`` over the engines' counters."""


def read(run):
    steps = run.engine.get("steps", 0)
    if not steps:
        return None
    return 100.0 * run.engine["occupancy_sum"] / steps / run.num_slots
