"""Output tokens served per tick in the window, over the engine's slots:
how full the scheduler keeps the decode batch."""


def read(obs):
    ticks = obs.window_ticks()
    if not ticks:
        return None
    n = sum(1 for r in obs.records for t in r.token_t if obs.in_window(t))
    return n / len(ticks) / obs.slots
