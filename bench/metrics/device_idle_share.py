"""Share of the traced slice of the window in which no operation ran on
the device: 1 - busy / slice, from the profiler trace."""


def read(obs):
    if obs.trace is None:
        return None
    return 1.0 - obs.trace["busy_s"] / obs.trace["window_s"]
