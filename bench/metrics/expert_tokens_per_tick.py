"""Tokens each held expert computed per decode tick in the window: the
(expert layer, pick) pairs on held experts of the decoded tokens that
reached the host in the window (``Request.expert_picks``, which the program
records beside ``out_tokens`` where its steps count them), over the decode
ticks that emitted them, the expert layers and the held experts.

A request's first token carries its whole prompt's picks, so it is left
out, and with it the prefill that fills the batch as the window opens: the
reading is the load a full decode batch puts on each held expert (rows x
top-k x held share / held experts), which fuller batches raise.  Tokens of
one tick share its host time, so the decode ticks are the distinct times
of the decoded tokens.  None where the program records no picks."""


def read(obs):
    cfg = obs.cfgfile
    if "n_routed_experts" not in cfg or "hybrid_override_pattern" not in cfg:
        return None
    picks, ticks, counted = 0, set(), False
    for rec in obs.records:
        per_token = getattr(rec.req, "expert_picks", None)
        if not per_token:
            continue
        counted = True
        for t, n in list(zip(rec.token_t, per_token))[1:]:
            if obs.in_window(t):
                picks += n
                ticks.add(t)
    if not counted or not ticks:
        return None
    layers = cfg["hybrid_override_pattern"].count("E")
    return picks / (len(ticks) * layers * cfg["n_routed_experts"])
