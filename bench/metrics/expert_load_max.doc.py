"""Load of the busiest held expert: its (token, expert) pairs over the
mean held expert's, over the window (the program's per-expert counter,
prefill and decode)."""


def read(ctx):
    pair = ctx["counters"]
    if pair is None or pair[0] is None or "pairs_per_expert" not in pair[0]:
        return None
    n = [b - a for a, b in zip(pair[0]["pairs_per_expert"],
                               pair[1]["pairs_per_expert"])]
    if not n or not sum(n):
        return None
    return float(max(n) * len(n) / sum(n))
