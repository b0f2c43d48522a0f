"""How unevenly the window's (token, expert) pairs fell on the experts:
in each expert layer the busiest expert's pairs over the mean of the
layer's experts, the largest of the layers' ratios. From the difference
of the engine's device-resident counter (``stats()["engine"]
["expert_counts"]``) across the window: both programs' pairs, neither a
prompt's padding nor a decode step's inactive rows. 1 is an even load."""

from benchmark import harness


def read(summary):
    pairs = summary.get("expert_pairs")
    if not pairs or not all(sum(layer) for layer in pairs):
        return None
    ratios = [max(layer) * len(layer) / sum(layer) for layer in pairs]
    harness.say("expert_load_max_over_mean.serve: by layer "
                + ", ".join(f"{r:.3f}" for r in ratios))
    return max(ratios)
