"""The work a round or a fold needs, from the cohort's shapes and live
ranks alone -- the numerator of every roofline and ``mfu`` share.

Only live rows count: a client at rank ``k`` sends ``k`` rows of each A
and ``k`` columns of each B per layer, whatever its storage rank.
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

WIRE_BYTES = {"none": 4, "int8": 1}
F32 = 4


def _sides(widths: dict):
    """Row widths of every pair side: A rows span fan_in, B's packed rows
    (its columns) span fan_out."""
    for fo, fi in widths.values():
        yield fi
        yield fo


def round_work(widths: dict, layers: int, r_max: int, ranks,
               codec: str) -> dict:
    """Necessary ``bytes`` and ``flops`` of one sync RBLA round."""
    wire = WIRE_BYTES[codec]
    owned = min(max(ranks), r_max)
    nbytes = flops = 0
    for width in _sides(widths):
        live = sum(min(k, r_max) for k in ranks) * layers
        nbytes += live * width * wire
        flops += live * width * (2 + (codec == "int8"))
        if codec == "int8":
            nbytes += live * F32
        nbytes += owned * layers * width * F32          # output written
        flops += owned * layers * width
    return {"bytes": nbytes, "flops": flops}


def fold_work(widths: dict, layers: int, r_max: int, rank: int,
              codec: str) -> dict:
    """Necessary ``bytes`` and ``flops`` of folding one upload."""
    wire = WIRE_BYTES[codec]
    live_rows = min(rank, r_max) * layers
    nbytes = flops = 0
    for width in _sides(widths):
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
