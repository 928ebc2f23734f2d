"""Executables built or loaded inside the measured window (kind train); must
read 0."""


def read(obs):
    if obs.get("kind") != "train":
        return None
    return obs.get("compiles_in_window")
