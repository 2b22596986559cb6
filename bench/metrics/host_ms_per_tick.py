"""Host time per tick, ms: the harness's span around each ``tick()`` in
the window, less the time the engine spent blocked waiting for the device
(growth of ``EngineStats.collect_wait_s``), over the window's ticks."""


def read(obs):
    ticks = obs.window_ticks()
    if not ticks:
        return None
    span = sum(b - a for a, b, _ in ticks)
    return (span - obs.collect_wait_s) / len(ticks) * 1e3
