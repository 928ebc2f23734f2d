"""Device idle share of the traced window: 1 - (union of the intervals in
which an operation ran on the device) / window, averaged over the chips
(kind train)."""


def read(obs):
    tr = obs.get("trace")
    if obs.get("kind") != "train" or not tr or not tr.get("window_s") \
            or not tr.get("n_ops"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
