"""The check catches a broken expert layer: the harness runs a scratch cell
on the tiny Nemotron file with the held experts' weights broken underneath
it, and ``correct`` comes out false.  Each fault stands for a mistake the
expert layer could make: the correction bias left out of the choice, the
held range off by one (each expert computed with its neighbour's weights),
and the routed scale left off the gates."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT), str(ROOT / "src"), str(Path(__file__).parent)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import cellfiles  # noqa: E402

NEMOTRON = cellfiles.DATA / "nemotron-tiny.json"
# The limit of the scratch cell at this size, from the CPU: sound runs read
# 0.0-0.059 over seeds 78-85 (bf16 rounding flips a few top-2 picks), the
# mildest fault, gates unscaled, 0.150-0.659; seed 78 reads 0.0 sound and
# 0.29 / 0.57 / 0.89 for the three faults
LIMIT = 0.1


def _broken(engine, leaf_fault):
    """Replace every expert-layer leaf of ``engine.params`` by
    ``leaf_fault(path, leaf)`` (None keeps the leaf)."""
    import jax

    def swap(path, leaf):
        got = leaf_fault(jax.tree_util.keystr(path), leaf)
        return leaf if got is None else got

    engine.params = jax.tree_util.tree_map_with_path(swap, engine.params)


def _bias_ignored(path, leaf):
    import jax.numpy as jnp
    return jnp.zeros_like(leaf) if path.endswith("['router']['bias']") \
        else None


def _held_range_shifted(path, leaf):
    import jax.numpy as jnp
    # (reps, tp, held experts, ...): expert e gets expert e-1's weights
    return jnp.roll(leaf, 1, axis=2) if "['experts']" in path else None


def _gates_unscaled(path, leaf):
    import jax.numpy as jnp
    if not path.endswith("['experts']['w_down']"):
        return None
    scale = json.loads(NEMOTRON.read_text())["routed_scaling_factor"]
    return (leaf.astype(jnp.float32) / scale).astype(leaf.dtype)


def _run(tmp_path, capsys, monkeypatch, fault=None):
    from bench import program, run
    greedy_rows = program.greedy_rows

    def broken(engine, rids):
        greedy_rows(engine, rids)
        if fault is not None:
            _broken(engine, fault)

    monkeypatch.setattr(program, "greedy_rows", broken)
    bench_file = cellfiles.write(tmp_path, json.loads(NEMOTRON.read_text()))
    cell_file = tmp_path / "bench" / "cells" / "tiny-chat.json"
    cell = json.loads(cell_file.read_text())
    cell["check"]["limit"] = LIMIT
    cell_file.write_text(json.dumps(cell))
    assert run.main(cellfiles.argv(bench_file, seed=78),
                    require_chip=False) == 0
    return cellfiles.last_json(capsys.readouterr().out)


@pytest.mark.parametrize("fault", [None, _bias_ignored, _held_range_shifted,
                                   _gates_unscaled])
def test_broken_expert_layer_is_not_correct(fault, tmp_path, capsys,
                                            monkeypatch):
    out = _run(tmp_path, capsys, monkeypatch, fault)
    chk = out["check"]["served_gap"]
    if fault is None:               # the sound program, for comparison
        assert out["correct"] is True and chk["value"] <= chk["limit"]
    else:
        assert out["correct"] is False and chk["value"] > chk["limit"]
