"""Inter-token gaps of the window, shared by the itl_* readers."""


def gaps_ms(obs):
    """Gaps between consecutive tokens of one request, in ms, over every
    gap whose later token reached the host inside the window."""
    out = []
    for r in obs.records:
        t = r.token_t
        out.extend((t[i] - t[i - 1]) * 1e3 for i in range(1, len(t))
                   if obs.in_window(t[i]))
    return out
