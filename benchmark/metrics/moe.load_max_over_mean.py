"""Routed-expert layer: the pairs of the most loaded expert held here over
the mean of the held experts, of the worst layer, as the timed step's own
metrics give it at each metrics sync (`moe_load_max_over_mean`); the mean
over the window's syncs. 1 is an even routing; the grouped products' row
tiles fill worse as it grows."""

KEY = "moe_load_max_over_mean"


def read(run):
    vals = [s[2][KEY] for s in run["syncs"] if KEY in s[2]]
    return sum(vals) / len(vals) if vals else None
