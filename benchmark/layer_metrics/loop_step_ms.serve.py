"""Window seconds over the serving loop's steps of every kind."""


def read(obs):
    if obs.get("kind") != "serve" or not obs.get("loop_steps"):
        return None
    return 1e3 * obs["window_s"] / obs["loop_steps"]
