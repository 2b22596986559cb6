"""Set-up, s: from the start of the run to the end of warm-up (weights,
engine build, pools, every compile or compile-cache load the cell's shapes
need)."""


def read(obs):
    return obs.setup_s
