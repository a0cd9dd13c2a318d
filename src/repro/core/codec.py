"""Upload codecs for LoRA adapter transport (RBLA PR 8).

At FLaaS scale the binding cost is upload bytes, not FLOPs: every client
ships fp32 ``(A, B)`` factors each round.  This module defines the wire
formats clients apply *before* ``AsyncAggregator.submit``:

``none``
    fp32 pass-through (bit-exact baseline).
``bf16``
    plain ``astype(bfloat16)`` cast -- 2x smaller, exact for values whose
    mantissa fits in 8 bits.
``int8``
    symmetric per-row quantization on the *packed row convention* from
    :func:`repro.core.plan.pair_side_rows`: each of ``A``'s rank rows
    (``amax`` over the fan-in axis) and each of ``B``'s rank *columns*
    (``amax`` over the fan-out axis -- the packed layer transposes B, so
    its packed rows are columns) carries one fp32 scale
    ``max|row| / 127``; payload is ``clip(round(x / scale), -127, 127)``
    as int8.  ~4x smaller; scales travel as runtime data so the plan
    layer's per-(width, dtype) bucket cache survives and dequantization
    fuses into ``packed_agg`` -- no fp32 staging buffer is materialized.

An encoded int8 pair is the usual ``{"A", "B", "rank"}`` mapping plus
``"A_scale"`` / ``"B_scale"`` entries of shape ``(..., r_max)``; the pair
walkers in :mod:`repro.core.plan` test key *containment*, so encoded
pairs flow through the same pytrees.  ``decode_pair`` is idempotent on
plain fp32 pairs, which keeps server paths codec-agnostic.

The server-side half of quantized transport lives here too:
:func:`stochastic_round` (f32 -> bf16 with mantissa-noise rounding, the
olmax-style trick for unbiased low-precision accumulators) backs the
``accum_dtype="bfloat16"`` fold state in
:class:`repro.fl.async_agg.AsyncAggregator`.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import host_syncs

#: registered codec names, in negotiation-preference order.
CODECS = ("none", "bf16", "int8")

_INT8_QMAX = 127.0

_SYNCS_SCALE = host_syncs("validate_scale")


# ----------------------------------------------------------- tree walk ----
# local pair predicates (repro.lora imports repro.core.masks; importing
# repro.lora from here would cycle through the package __init__)
def _is_pair(node: Any) -> bool:
    return (isinstance(node, Mapping) and "A" in node and "B" in node
            and "rank" in node)


def _map_pairs(fn, tree):
    if _is_pair(tree):
        return fn(tree)
    if isinstance(tree, Mapping):
        return {k: _map_pairs(fn, v) for k, v in tree.items()}
    if isinstance(tree, (tuple, list)):
        return type(tree)(_map_pairs(fn, v) for v in tree)
    return tree


def _iter_pairs(tree, path=()):
    if _is_pair(tree):
        yield path, tree
        return
    if isinstance(tree, Mapping):
        for k, v in tree.items():
            yield from _iter_pairs(v, path + (k,))
    elif isinstance(tree, (tuple, list)):
        for i, v in enumerate(tree):
            yield from _iter_pairs(v, path + (i,))


def _dtype_of(x) -> np.dtype:
    """A leaf's dtype, read on the host: a numpy leaf is not copied to the
    device to learn it."""
    dtype = getattr(x, "dtype", None)
    return np.dtype(dtype) if dtype is not None else np.result_type(x)


# -------------------------------------------------------------- codecs ----
def codec_of_pair(pair: Mapping) -> str:
    """Wire format of one (possibly encoded) pair."""
    if "A_scale" in pair or "B_scale" in pair:
        return "int8"
    if _dtype_of(pair["A"]) == jnp.bfloat16:
        return "bf16"
    return "none"


def tree_codec(adapters) -> str:
    """Codec of a whole adapter tree; ``"mixed"`` if pairs disagree."""
    seen = {codec_of_pair(p) for _, p in _iter_pairs(adapters)}
    if not seen:
        return "none"
    return seen.pop() if len(seen) == 1 else "mixed"


def cohort_codecs(client_adapters: Sequence) -> tuple | None:
    """Per-client codec names for a cohort, or ``None`` when every client
    uploaded plain fp32 (the fast path: zero codec overhead)."""
    codecs = tuple(tree_codec(a) for a in client_adapters)
    return None if all(c == "none" for c in codecs) else codecs


def _int8_encode_side(x, row_axis: int):
    """Quantize one factor along the packed-row axis.

    ``row_axis=-1`` treats trailing-axis vectors as rows (A); ``-2``
    quantizes columns (B, whose packed rows are columns).  Returns
    ``(q_int8, scale)`` with ``scale`` of shape ``x.shape`` minus the
    reduced axis -- ``(..., r_max)`` either way."""
    xf = jnp.asarray(x, jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=row_axis)
    scale = jnp.where(amax > 0, amax / _INT8_QMAX, 1.0)
    s = jnp.expand_dims(scale, row_axis)
    q = jnp.clip(jnp.round(xf / s), -_INT8_QMAX, _INT8_QMAX)
    return q.astype(jnp.int8), scale.astype(jnp.float32)


def encode_pair(pair: Mapping, codec: str) -> dict:
    """Encode one pair for upload.  ``rank`` always stays exact."""
    if codec == "none":
        return dict(pair)
    if codec == "bf16":
        out = dict(pair)
        out["A"] = jnp.asarray(pair["A"]).astype(jnp.bfloat16)
        out["B"] = jnp.asarray(pair["B"]).astype(jnp.bfloat16)
        return out
    if codec == "int8":
        qa, sa = _int8_encode_side(pair["A"], row_axis=-1)
        qb, sb = _int8_encode_side(pair["B"], row_axis=-2)
        out = dict(pair)
        out.update(A=qa, B=qb, A_scale=sa, B_scale=sb)
        return out
    raise ValueError(f"unknown codec {codec!r}; options: {list(CODECS)}")


def decode_pair(pair: Mapping) -> dict:
    """Dequantize one pair to fp32.  Idempotent on plain pairs."""
    codec = codec_of_pair(pair)
    if codec == "none":
        return dict(pair)
    out = {k: v for k, v in pair.items()
           if k not in ("A_scale", "B_scale")}
    if codec == "bf16":
        out["A"] = jnp.asarray(pair["A"]).astype(jnp.float32)
        out["B"] = jnp.asarray(pair["B"]).astype(jnp.float32)
        return out
    sa = jnp.asarray(pair["A_scale"], jnp.float32)
    sb = jnp.asarray(pair["B_scale"], jnp.float32)
    out["A"] = jnp.asarray(pair["A"]).astype(jnp.float32) * sa[..., :, None]
    out["B"] = jnp.asarray(pair["B"]).astype(jnp.float32) * sb[..., None, :]
    return out


def encode_adapters(adapters, codec: str):
    """Encode every pair in an adapter tree; non-pair leaves untouched."""
    if codec not in CODECS:
        raise ValueError(f"unknown codec {codec!r}; options: {list(CODECS)}")
    if codec == "none":
        return adapters
    return _map_pairs(lambda p: encode_pair(p, codec), adapters)


def decode_adapters(adapters):
    """Dequantize every pair in a tree to fp32 (idempotent)."""
    return _map_pairs(decode_pair, adapters)


def encode_update(update, codec: str):
    """Encode a ``ClientUpdate``'s adapters (``base_trainable`` stays
    fp32 -- base rows are shared-dense and fold through plain FedAvg,
    outside the packed-plan codec contract)."""
    return dataclasses.replace(update,
                               adapters=encode_adapters(update.adapters,
                                                        codec))


def decode_update(update):
    """Dequantize a ``ClientUpdate`` (idempotent on plain updates)."""
    return dataclasses.replace(update,
                               adapters=decode_adapters(update.adapters))


# ---------------------------------------------------------- validation ----
class UploadValidationError(ValueError):
    """A rejected upload, tagged with the machine-readable ``reason``
    the ingestion metrics count it under (``fl_updates_rejected_total``;
    see ``docs/observability.md`` for the reason catalog).  Subclasses
    ``ValueError`` so existing ``except ValueError`` call sites and
    ``pytest.raises(ValueError, match=...)`` tests keep working."""

    def __init__(self, message: str, reason: str):
        super().__init__(message)
        self.reason = reason


def _scale_limit(width: int) -> float:
    """Largest int8 scale whose decoded row norm, ``scale * 127 *
    sqrt(width)``, stays inside float32."""
    return float(jnp.finfo(jnp.float32).max) / (
        _INT8_QMAX * math.sqrt(max(width, 1)))


@functools.partial(jax.jit, static_argnames=("widths",))
def _upload_flags(adapter_floats, base_floats, scales, widths):
    """Every device check of one upload in one program: ``[adapters all
    finite, base_trainable all finite]``, then per scale plane ``bad``
    (non-finite or non-positive) and ``overflow`` (past
    :func:`_scale_limit` of its static row width)."""
    def finite(leaves):
        return jnp.all(jnp.stack([jnp.all(jnp.isfinite(x)) for x in leaves]
                                 or [jnp.array(True)]))
    flags = [finite(adapter_floats), finite(base_floats)]
    for s, width in zip(scales, widths):
        s = s.astype(jnp.float32)
        flags += [~jnp.all(jnp.isfinite(s) & (s > 0)),
                  jnp.any(s > _scale_limit(width))]
    return jnp.stack(flags)


def _float_leaves(tree) -> list:
    return [x for x in jax.tree.leaves(tree)
            if jnp.issubdtype(_dtype_of(x), jnp.floating)]


def _scale_planes(adapters) -> list:
    """``(name, plane, row width)`` of every int8 scale plane, in walk
    order, ``A_scale`` before ``B_scale`` within a pair."""
    planes = []
    for path, pair in _iter_pairs(adapters):
        name = "/".join(str(p) for p in path) or "<root>"
        for side, key in (("A", "A_scale"), ("B", "B_scale")):
            if key in pair:
                shape = np.shape(pair[side])
                planes.append((f"{name}.{key}", pair[key],
                               shape[-1] if side == "A" else shape[-2]))
    return planes


def _devices(x):
    return (frozenset(x.sharding.device_set) if isinstance(x, jax.Array)
            else None)


def _read_flags(adapter_floats: list, base_floats: list,
                planes: list) -> tuple:
    """Run :func:`_upload_flags` over the given leaves and read every
    flag with one ``jax.device_get``.

    Leaves committed to different devices cannot meet in one program:
    each device set runs its own (host leaves join the first), and the
    one read takes all their flags."""
    scales = [plane for _, plane, _ in planes]
    lists = (adapter_floats, base_floats, scales)
    keys = [[_devices(x) for x in xs] for xs in lists]
    first = next((k for ks in keys for k in ks if k is not None), None)
    # device set -> (adapter floats, base floats, indices into planes)
    groups = {}
    for n, (xs, ks) in enumerate(zip(lists, keys)):
        for i, (x, k) in enumerate(zip(xs, ks)):
            group = groups.setdefault(first if k is None else k,
                                      ([], [], []))
            group[n].append(i if n == 2 else x)
    if not groups:
        return True, True, [], 0
    flags = jax.device_get([
        _upload_flags(a, b, [scales[i] for i in mine],
                      widths=tuple(planes[i][2] for i in mine))
        for a, b, mine in groups.values()])
    by_plane = {}
    for (_, _, mine), f in zip(groups.values(), flags):
        by_plane.update((i, (bool(f[2 + 2 * j]), bool(f[3 + 2 * j])))
                        for j, i in enumerate(mine))
    reads = int(any(isinstance(x, jax.Array) for xs in lists for x in xs))
    return (all(f[0] for f in flags), all(f[1] for f in flags),
            [(name, *by_plane[i]) for i, (name, _, _) in enumerate(planes)],
            reads)


def read_upload_flags(adapters, base_trainable) -> tuple:
    """All device checks of one upload -- every float leaf of both trees,
    every int8 scale plane -- in one compiled program, read once.

    Returns ``(adapters_finite, base_finite, [(plane name, bad,
    overflow)], reads)``: ``reads`` is 1 when a checked leaf was a
    ``jax.Array`` (a read the host waited on), else 0.  Raise the scale
    faults with :func:`raise_scale_faults`."""
    return _read_flags(_float_leaves(adapters),
                       _float_leaves(base_trainable),
                       _scale_planes(adapters))


def raise_scale_faults(per_plane: list) -> None:
    """Raise the first scale fault in walk order, ``bad_scale`` before
    ``overflow`` within a plane (see :func:`validate_encoded_adapters`)."""
    for name, bad, overflow in per_plane:
        if bad:
            raise UploadValidationError(
                f"non-finite or non-positive quantization scale in {name}",
                reason="bad_scale")
        if overflow:
            raise UploadValidationError(
                f"quantization scale overflow in {name}: decoded row norm "
                f"would exceed float32 range", reason="overflow")


def validate_encoded_adapters(adapters) -> None:
    """Ingestion sanity for encoded uploads (one compiled check, one read).

    Raises :class:`UploadValidationError` (a ``ValueError``) when any
    quantization scale is non-finite or non-positive (``reason
    "bad_scale"``), or when an int8 payload's decoded norm would overflow
    fp32 (``scale * 127 * sqrt(row_width)`` past ``finfo(f32).max`` --
    such an upload would poison ``FoldState`` masses irrecoverably;
    ``reason "overflow"``)."""
    *_, per_plane, reads = _read_flags([], [], _scale_planes(adapters))
    _SYNCS_SCALE.inc(reads)
    raise_scale_faults(per_plane)


# ---------------------------------------------- stochastic accumulators ----
def stochastic_round(x, key, dtype=jnp.bfloat16):
    """Round f32 -> ``dtype`` (bf16) stochastically, olmax-style.

    Adds 16 uniform random bits to the f32 bit pattern and truncates the
    low mantissa half: ``bf16(bitcast(bitcast_u32(x) + u16) &
    0xFFFF0000)``.  Rounds up with probability ``frac/ulp``, so
    ``E[round(x)] == x`` exactly; bf16-representable values (low 16 bits
    zero) are fixed points regardless of the noise.  Non-finite inputs
    pass through unchanged (carry past the exponent would corrupt them;
    ingestion rejects them anyway)."""
    if jnp.dtype(dtype) != jnp.bfloat16:
        raise ValueError("stochastic_round targets bfloat16 storage; got "
                         f"{jnp.dtype(dtype)}")
    xf = jnp.asarray(x, jnp.float32)
    bits = jax.lax.bitcast_convert_type(xf, jnp.uint32)
    try:
        noise = jax.random.bits(key, xf.shape, jnp.uint32)
    except (AttributeError, TypeError):   # older jax: no random.bits
        noise = jax.random.randint(key, xf.shape, 0, 1 << 16,
                                   jnp.int32).astype(jnp.uint32)
    bits = (bits + (noise & jnp.uint32(0xFFFF))) & jnp.uint32(0xFFFF0000)
    rounded = jax.lax.bitcast_convert_type(bits, jnp.float32)
    rounded = jnp.where(jnp.isfinite(xf), rounded, xf)
    return rounded.astype(dtype)


def stochastic_round_tree(tree, key, dtype=jnp.bfloat16):
    """Per-leaf :func:`stochastic_round` over the float leaves of a
    pytree (integer leaves -- ``rank`` vectors, counters -- untouched).
    One key split per leaf keeps leaves independent and the whole map a
    pure function of ``(tree, key)``."""
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(key, max(len(leaves), 1))
    out = [stochastic_round(leaf, k, dtype)
           if jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.floating) else leaf
           for leaf, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, out)


__all__ = [
    "CODECS", "codec_of_pair", "tree_codec", "cohort_codecs",
    "encode_pair", "decode_pair", "encode_adapters", "decode_adapters",
    "encode_update", "decode_update", "validate_encoded_adapters",
    "read_upload_flags", "raise_scale_faults", "UploadValidationError",
    "stochastic_round", "stochastic_round_tree",
]
