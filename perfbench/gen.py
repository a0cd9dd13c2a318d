"""Inputs of a cell, made from ``--seed``: uploads, the first global, and
each round's or fold's draw.

The upload pool is built on the device by one jitted program per codec,
in the type it is served in (f32, or the int8 wire format with its f32 scale
planes).  Every pair is checked against the configuration file's own
widths before anything is generated, so a change to the program's
adapter layout cannot silently change what is measured.

Host draws (cohort order, weights, staleness) come from NumPy generators
seeded with ``(seed, stream, index)``: round ``k`` or ring cycle ``c``
draws the same values whatever ran before it, which lets the reference
re-derive any round from the seed alone.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

#: weights (n_examples) are drawn from this stream; cohort orders from 0
_ORDER, _WEIGHTS = 0, 1


def is_pair(node) -> bool:
    return isinstance(node, dict) and {"A", "B", "rank"} <= node.keys()


def map_pairs(fn, tree, path=()):
    """``fn(path, pair)`` on every LoRA pair of a nested dict/tuple tree."""
    if is_pair(tree):
        return fn(path, tree)
    if isinstance(tree, dict):
        return {k: map_pairs(fn, v, path + (k,)) for k, v in tree.items()}
    if isinstance(tree, (tuple, list)):
        return type(tree)(map_pairs(fn, v, path + (i,))
                          for i, v in enumerate(tree))
    return tree


def pair_list(tree) -> list:
    """``[(path, pair), ...]`` in tree order."""
    out = []
    map_pairs(lambda p, v: out.append((p, v)), tree)
    return out


def base_key(seed: int):
    """A PRNG key that uses every bit of a seed of up to 64 bits
    (``PRNGKey`` alone keeps only the low 32)."""
    seed = int(seed) % 2 ** 64
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def host_rng(seed: int, stream: int, index: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2 ** 64, stream, index])


# ----------------------------------------------------------- the layout ----
@dataclasses.dataclass
class Layout:
    """Adapter tree of one client: the program's container structure with
    every pair's shapes taken from the configuration file."""
    template: object           # the program's tree of ShapeDtypeStructs
    layers: int
    r_max: int
    widths: dict               # target -> (fan_out, fan_in)
    makers: dict = dataclasses.field(default_factory=dict)

    def maker(self, codec: str):
        """The jitted one-upload generator for ``codec`` (traced once)."""
        if codec not in self.makers:
            def upload(key, rank):
                return _client(self, codec, key, rank)
            self.makers[codec] = jax.jit(upload)
        return self.makers[codec]


def program_layout(config: dict) -> Layout:
    """Build the client adapter layout through the program's public
    ``Model.init_adapters`` (shapes only) and assert every pair against
    the configuration file: ``A (L, r_max, fan_in)``, ``B (L, fan_out,
    r_max)``, ``rank (L,)``, one pair per listed target and no other."""
    from repro.configs import get_config
    from repro.configs.base import Stage
    from repro.models.model import make_model

    ad = config["adapter"]
    layers = int(config[ad["layers_key"]])
    r_max = int(ad["r_max"])
    widths = {t: (int(fo), int(fi)) for t, (fo, fi) in ad["targets"].items()}
    arch = get_config(ad["arch"])
    arch = dataclasses.replace(
        arch, stages=(Stage(unit=arch.stages[0].unit, repeat=layers),),
        lora_r_max=r_max, **config.get("program_overrides", {}))
    template = jax.eval_shape(
        lambda k: make_model(arch).init_adapters(k, r_max=r_max),
        jax.random.PRNGKey(0))
    seen = []
    for path, pair in pair_list(template):
        target = path[-1]
        if target not in widths:
            raise ValueError(f"program adapts {path}, which the "
                             "configuration file does not list")
        fo, fi = widths[target]
        want = {"A": (layers, r_max, fi), "B": (layers, fo, r_max),
                "rank": (layers,)}
        got = {k: tuple(pair[k].shape) for k in want}
        if got != want:
            raise ValueError(f"pair {path}: program shapes {got} differ "
                             f"from the configuration's {want}")
        seen.append(target)
    if sorted(seen) != sorted(widths):
        raise ValueError(f"program adapts {sorted(seen)}, configuration "
                         f"lists {sorted(widths)}")
    return Layout(template=template, layers=layers, r_max=r_max,
                  widths=widths)


# ------------------------------------------------------------- uploads ----
def pool_ranks(traffic: dict, r_max: int, size: int) -> list:
    """Ranks of a pool of ``size`` uploads in class order, from the
    traffic's ``rank_mix`` (``[[divisor, count], ...]`` per ``unit``)."""
    unit = sum(c for _, c in traffic["rank_mix"])
    if size % unit:
        raise ValueError(f"pool of {size} is not a whole number of rank "
                         f"mixes of {unit}")
    return [r_max // d for d, c in traffic["rank_mix"]
            for _ in range(c * size // unit)]


def _client(layout: Layout, codec: str, key, rank):
    """One upload: live rows (A) / columns (B) below ``rank`` random,
    padding zero, as a trained and masked LoRA pair would be."""
    L, r = layout.layers, layout.r_max
    live = jnp.arange(r) < rank
    index = {p: i for i, (p, _) in enumerate(pair_list(layout.template))}

    def make(path, pair):
        k = jax.random.fold_in(key, index[path])
        ka, kb, ksa, ksb = jax.random.split(k, 4)
        fo, fi = layout.widths[path[-1]]
        out = {"rank": jnp.full((L,), rank, jnp.int32)}
        for side, kx, ks, shape, mask in (
                ("A", ka, ksa, (L, r, fi), live[:, None]),
                ("B", kb, ksb, (L, fo, r), live[None, :])):
            if codec == "int8":
                q = jax.random.randint(kx, shape, -127, 128, jnp.int8)
                out[side] = jnp.where(mask, q, 0).astype(jnp.int8)
                s = jax.random.uniform(ks, (L, r), jnp.float32, 1e-4, 1e-3)
                # the codec's scale of an all-zero row is 1
                out[side + "_scale"] = jnp.where(live, s, 1.0)
            elif codec == "none":
                x = jax.random.uniform(kx, shape, jnp.float32, -0.05, 0.05)
                out[side] = jnp.where(mask, x, 0.0)
            else:
                raise ValueError(f"unknown codec {codec!r}")
        return out
    return map_pairs(make, layout.template)


def make_upload(layout: Layout, seed: int, rank: int, codec: str,
                stream: int, index: int):
    """Upload ``index`` of ``stream`` (``stream`` separates the pools made
    from one seed), made on the device by the codec's jitted program."""
    key = jax.random.fold_in(jax.random.fold_in(base_key(seed), stream),
                             index)
    return layout.maker(codec)(key, jnp.int32(rank))


def make_pool(layout: Layout, seed: int, ranks, codec: str, stream: int):
    """``len(ranks)`` uploads of ``stream``, one call of the jitted
    program each, with its key and rank as data (one program that
    unrolls a pool of 128 uploads is too large to compile)."""
    return [make_upload(layout, seed, rk, codec, stream, i)
            for i, rk in enumerate(ranks)]


def make_global(layout: Layout, seed: int):
    """The first global: every row live, f32, rank leaves at ``r_max``."""
    return make_pool(layout, seed, [layout.r_max], "none", stream=1_000)[0]


# ----------------------------------------------------------- host draws ----
def round_draw(seed: int, k: int, n: int, weights: dict):
    """Round ``k``: the cohort's order over the pool and its weights."""
    rng = host_rng(seed, _ORDER, k)
    order = rng.permutation(n)
    w = host_rng(seed, _WEIGHTS, k).integers(
        weights["low"], weights["high"] + 1, n).astype(np.float32)
    return order, w


class FoldStream:
    """The async cell's submissions: ring cycle ``c`` visits the ring in
    a seeded order, never starting with the upload that ended cycle
    ``c - 1``, with seeded weights and staleness."""

    def __init__(self, seed: int, ring: int, weights: dict,
                 staleness_max: int):
        self.seed, self.ring = seed, ring
        self.weights, self.staleness_max = weights, staleness_max
        self._cycles: list = []

    def _cycle(self, c: int):
        while len(self._cycles) <= c:
            i = len(self._cycles)
            rng = host_rng(self.seed, _ORDER, i)
            order = rng.permutation(self.ring)
            if i and order[0] == self._cycles[-1][0][-1]:
                order[[0, 1]] = order[[1, 0]]
            n_ex = host_rng(self.seed, _WEIGHTS, i).integers(
                self.weights["low"], self.weights["high"] + 1, self.ring)
            tau = rng.integers(0, self.staleness_max + 1, self.ring)
            self._cycles.append((order, n_ex, tau))
        return self._cycles[c]

    def __getitem__(self, j: int):
        """``(upload index, n_examples, staleness)`` of submission j."""
        order, n_ex, tau = self._cycle(j // self.ring)
        i = j % self.ring
        return int(order[i]), float(n_ex[i]), int(tau[i])
