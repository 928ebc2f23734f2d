"""loop.mean_occupancy(): rows stepped over max_batch, mean over steps."""


def read(obs):
    if obs.get("kind") != "serve" or obs.get("occupancy") is None:
        return None
    return 100.0 * obs["occupancy"]
