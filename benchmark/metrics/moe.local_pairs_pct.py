"""Routed-expert layer: the (frame, expert) pairs computed here a step
(`moe_local_pairs` of the timed step's own metrics, all layers, mean over
the window's syncs) over the pairs the router makes, frames x experts a
frame x layers. An even routing reads experts held over experts: 25 where
16 of 64 are held."""

KEY = "moe_local_pairs"


def read(run):
    vals = [s[2][KEY] for s in run["syncs"] if KEY in s[2]]
    pol = run["config"]["policy"]
    if not vals or not pol.get("moe_top_k"):
        return None
    frames = run["rows_per_step"] * (int(run["config"]["learner"]["seq_len"]) + 1)
    return 100.0 * sum(vals) / len(vals) / (frames * int(pol["moe_top_k"]) * int(pol["tf_layers"]))
