"""Device time of the collective-permute ops that carry the two-tree
schedule's ppermute rounds, per rollout, ms: the union of those ops'
intervals on each chip, averaged over the chips."""


def read(view):
    per_chip = view.op_seconds(r"collective-permute")
    if not per_chip or max(per_chip) <= 0 or view.units == 0:
        return None
    return sum(per_chip) / len(per_chip) / view.units * 1e3
