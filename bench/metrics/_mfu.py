"""The whole step's share of the chip's roofline over the window, in
percent (``bench.arith.whole_step_share``).  Decode is bound by memory,
so the byte term usually decides it."""
from bench import arith


def read(obs):
    if obs.peaks is None:
        return None
    costs = arith.model_costs(obs.cfgfile)
    return arith.whole_step_share(obs, costs, obs.weight_bytes, obs.peaks)
