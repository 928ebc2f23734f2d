"""kernels.paged_attention.fallback_count() over the window; must read 0."""


def read(obs):
    if obs.get("kind") != "serve":
        return None
    return obs.get("paged_fallbacks")
