"""Operations and bytes that the served work needs, and the chip's peaks.

The counts are what the algorithm needs at the configuration's stated
dtypes, not what the program happens to move: each decode token reads the
context it attends once (pages for attention, the recurrent state for a
state-space layer) and writes its own state; each prefill chunk reads the
context before it once; each tick that ran a step reads every weight once.
Matrix products count 2 operations per multiply-add.  ``whole_step_share``
turns the window's work into the share of the chip's roofline: the least
time the chip could have taken (the larger of operations over peak
FLOP/s and bytes over peak bandwidth) over the window's seconds.  Each
family's counts are its adapter's cost model (``bench/families/``).
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

from bench import families

PEAKS = Path(__file__).resolve().parent / "peaks.json"
ITEM = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


def peaks(device_kind: str) -> dict:
    """The peaks of one chip of this kind; an unknown kind is an error."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(k for k in table if not k.startswith('_'))}")
    return table[device_kind]


def weight_bytes(spec: dict) -> int:
    """Bytes of every served weight (a tied head counts once)."""
    return int(sum(np.prod(shape) * ITEM[dtype]
                   for shape, dtype, _, _ in spec.values()))


def model_costs(cfgfile: dict):
    """The cost model of a configuration file's family, from its adapter
    ``bench/families/<family>.py``."""
    return families.load(cfgfile["reference"]).costs(cfgfile)


def window_work(obs, costs, w_bytes: int):
    """-> (operations, bytes) of the work the window completed: decode
    tokens served in it with the context each attended, prompts whose
    first token came in it, and one read of the weights per tick in it
    that held a request."""
    fl = by = 0
    for rec in obs.records:
        L = len(rec.job.prompt)
        for k, t in enumerate(rec.token_t):
            if not obs.in_window(t):
                continue
            if k == 0:
                f, b = costs.prefill(L, obs.chunk)
            else:
                f, b = costs.decode_token(L + k)
            fl += f
            by += b
    by += w_bytes * sum(1 for _, _, busy in obs.window_ticks() if busy)
    return fl, by


def whole_step_share(obs, costs, w_bytes: int, peak: dict) -> float:
    """Percent of the chip's roofline the window's work would fill."""
    fl, by = window_work(obs, costs, w_bytes)
    t_min = max(fl / peak["bf16_flops_per_s"], by / peak["hbm_bytes_per_s"])
    return 100.0 * t_min / obs.window_s
