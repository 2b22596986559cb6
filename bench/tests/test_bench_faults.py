"""The check catches a broken timed path: the harness runs a scratch cell
with the engine broken underneath it, and ``correct`` comes out false."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT), str(ROOT / "src"), str(Path(__file__).parent)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import cellfiles  # noqa: E402


def _state_unchanged(engine):
    """The decode step computes its logits but hands back the cache it
    was given: no token's KV is ever written by decode."""
    import jax
    import jax.numpy as jnp
    decode = engine.decode_fn

    def step(*args):
        kept = jax.tree_util.tree_map(jnp.copy, args[1])
        return decode(*args)[0], kept

    engine.decode_fn = step


def _rows_swapped(engine):
    """The decode step hands each slot another slot's logits (the batch
    in reverse order), as a wrong slot index would."""
    decode = engine.decode_fn

    def step(*args):
        logits, cache = decode(*args)
        return logits[::-1], cache

    engine.decode_fn = step


def _token_altered(engine):
    """The fourth token of every request is replaced where it is sampled."""
    sample = engine._sample_row

    def row(logits, b, req):
        tok = sample(logits, b, req)
        return (tok + 1) % engine.cfg.vocab_size \
            if len(req.out_tokens) == 3 else tok

    engine._sample_row = row


@pytest.mark.parametrize("fault", [_state_unchanged, _rows_swapped,
                                   _token_altered])
def test_broken_timed_path_is_not_correct(fault, tmp_path, capsys,
                                          monkeypatch):
    from bench import program, run
    greedy_rows = program.greedy_rows

    def broken(engine, rids):
        greedy_rows(engine, rids)
        fault(engine)

    monkeypatch.setattr(program, "greedy_rows", broken)
    bench_file = cellfiles.write(tmp_path)
    assert run.main(cellfiles.argv(bench_file, seed=77),
                    require_chip=False) == 0
    out = cellfiles.last_json(capsys.readouterr().out)
    assert out["correct"] is False
    chk = out["check"]["served_gap"]
    assert chk["value"] > chk["limit"]
