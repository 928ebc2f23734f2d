"""Share of the serving loop's steps that were prefill steps."""


def read(obs):
    if obs.get("kind") != "serve" or not obs.get("loop_steps"):
        return None
    return 100.0 * obs["loop_prefill_steps"] / obs["loop_steps"]
