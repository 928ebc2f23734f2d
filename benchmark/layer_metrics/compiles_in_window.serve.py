"""Executables built or loaded inside the measured window (kind serve); must
read 0 after the warm pass."""


def read(obs):
    if obs.get("kind") != "serve":
        return None
    return obs.get("compiles_in_window")
