"""Peaks of the chip, and the work of one merge-gain call counted from its
shapes, so that every backend is read against the same work."""

from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an error."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE}; known: {sorted(table['devices'])}")
    return dict(table["devices"][device_kind], source=table["source"])


#: Arithmetic operations (add, sub, mul, div, max, min, log, neg, and one
#: add per element of a sum) of the default backend's body, counted from its
#: jaxpr by ``tests/test_roofline.py``: per (candidate pair, union column) of
#: Eq. (17), the merged count's pair cost, mask and column sum ...
FLOPS_PER_PAIR_COLUMN = 26
#: ... and per candidate pair: the self pair, the tails, the denominator and
#: the ratio. The per-member terms (1/C of the whole) are left out, so the
#: count errs low.
FLOPS_PER_PAIR = 58


def merge_gain_flops(g: int, c: int, u: int) -> float:
    """G·C²·(26U + 58): the arithmetic of every candidate pair."""
    return float(g) * c * c * (FLOPS_PER_PAIR_COLUMN * u + FLOPS_PER_PAIR)


def merge_gain_bytes(g: int, c: int, u: int) -> float:
    """4·G·(CU + 4C + U + 3C²): each float32/int32 operand read once
    (m [G,C,U]; n, s, t, cidx [G,C]; n_u [G,U]; w [G,C,C]) and the two
    [G,C,C] outputs written once."""
    return 4.0 * g * (c * u + 4 * c + u + 3 * c * c)


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak: dict) -> tuple[float, str]:
    """The least time the chip could take over the time taken, in percent,
    and which bound sets that least time."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    bound = "bytes" if t_bytes >= t_flops else "flops"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
