"""Output tokens that reached the host inside the window, per second of
the window."""


def read(obs):
    n = sum(1 for r in obs.records for t in r.token_t if obs.in_window(t))
    return n / obs.window_s
