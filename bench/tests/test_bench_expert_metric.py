"""``expert_tokens_per_tick`` reads the expert picks that the program
records beside each decoded token: over the window's decode ticks, expert
layers and held experts, and nothing where the program records none."""
import sys
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import loop, run, traffic  # noqa: E402

CFG = {"hybrid_override_pattern": "MEMEM*E", "n_routed_experts": 2}


def _obs(picks_of, cfgfile=CFG):
    recs = []
    for i, (times, picks) in enumerate(picks_of):
        req = types.SimpleNamespace(out_tokens=[0] * len(times))
        if picks is not None:
            req.expert_picks = picks
        recs.append(loop.Record(job=traffic.Job(index=i, prompt=[2, 3],
                                                max_new_tokens=len(times),
                                                greedy=True),
                                req=req, due=0.0, token_t=list(times)))
    obs = loop.Observation(records=recs, window=(10.0, 20.0),
                           ticks=[(10.0 + k, 10.5 + k, True)
                                  for k in range(10)],
                           collect_wait_s=0.0, slots=4, chunk=16,
                           lateness_s=[], waiting=[])
    obs.cfgfile = cfgfile
    return obs


def test_reads_held_picks_over_ticks_layers_and_experts():
    read = run.reader("expert_tokens_per_tick")
    # the 9.0 s token falls before the window, the 20.0 s one after it;
    # a first token (40, 30, 50: a whole prompt's picks) is never counted
    obs = _obs([([9.0, 10.5, 11.5], [40, 3, 2]), ([12.5, 20.0], [30, 4]),
                ([10.5, 11.5, 13.5], [50, 5, 6])])
    # decode ticks at 10.5, 11.5 and 13.5 s; 3 expert layers in MEMEM*E,
    # 2 held experts
    assert read(obs) == (3 + 2 + 5 + 6) / (3 * 3 * 2)


def test_reads_nothing_where_the_program_records_no_picks():
    read = run.reader("expert_tokens_per_tick")
    assert read(_obs([([10.5, 11.5], None)])) is None
    assert read(_obs([([10.5], [3])], cfgfile={})) is None
