"""The work a round or a fold needs, from the cohort's shapes and live
ranks alone -- the numerator of every roofline and ``mfu`` share.

Only live rows count: a client at rank ``k`` sends ``k`` rows of each A
and ``k`` columns of each B per layer and per expert held (per index of
the pair's leading axes), whatever its storage rank.
Padding rows never count, so a share reads the same work whatever layout
implements it, and no layout can push one past 100%.

Bytes:
  * sync round: every client's live rows at their wire dtype, plus one
    f32 scale per live row for int8; each output row that some client
    owns, written in f32.  Rows that no client owns keep the previous
    global's value in place and need no traffic.
  * fold: the upload's live rows at their wire dtype (plus scales), and
    the state's same rows read and written in f32, with their f32 row
    mass read and written.
FLOPs: a multiply-add per live client element (plus a multiply to
dequantize int8) and a divide per owned output element; a fold takes a
subtract, a multiply and an add per live element.
"""
from __future__ import annotations

import math

WIRE_BYTES = {"none": 4, "int8": 1}
F32 = 4


def _sides(pairs):
    """``(row width, rows per rank row)`` of every pair side: A rows span
    fan_in, B's packed rows (its columns) span fan_out, and a rank row
    repeats over the pair's leading axes (layers, experts held).
    ``pairs``: ``(fan_out, fan_in, lead)`` per pair."""
    for fo, fi, lead in pairs:
        n = math.prod(lead)
        yield fi, n
        yield fo, n


def round_work(pairs, r_max: int, ranks, codec: str) -> dict:
    """Necessary ``bytes`` and ``flops`` of one sync RBLA round."""
    wire = WIRE_BYTES[codec]
    owned = min(max(ranks), r_max)
    live_ranks = sum(min(k, r_max) for k in ranks)
    nbytes = flops = 0
    for width, n in _sides(pairs):
        live = live_ranks * n
        nbytes += live * width * wire
        flops += live * width * (2 + (codec == "int8"))
        if codec == "int8":
            nbytes += live * F32
        nbytes += owned * n * width * F32               # output written
        flops += owned * n * width
    return {"bytes": nbytes, "flops": flops}


def fold_work(pairs, r_max: int, rank: int, codec: str) -> dict:
    """Necessary ``bytes`` and ``flops`` of folding one upload."""
    wire = WIRE_BYTES[codec]
    nbytes = flops = 0
    for width, n in _sides(pairs):
        live_rows = min(rank, r_max) * n
        nbytes += live_rows * width * (wire + 2 * F32)
        nbytes += live_rows * 2 * F32                   # row mass
        if codec == "int8":
            nbytes += live_rows * F32
        flops += live_rows * width * 3
    return {"bytes": nbytes, "flops": flops}


def least_seconds(work: dict, peaks: dict, flop_peak: str) -> float:
    """The least time the chip could take: the larger of FLOPs over the
    named FLOP/s peak and bytes over HBM bandwidth."""
    return max(work["flops"] / peaks[flop_peak],
               work["bytes"] / peaks["hbm_bytes_per_s"])
