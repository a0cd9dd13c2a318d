"""Inputs of a cell, made from ``--seed``: uploads, the first global, and
each round's or fold's draw.

The upload pool is built on the device by one jitted program per codec,
in the type it is served in (f32, or the int8 wire format with its f32 scale
planes).  Every pair is checked against the configuration file's own
widths before anything is generated, so a change to the program's
adapter layout cannot silently change what is measured.

A configuration file's ``adapter`` states the adapter tree in one of two
forms.  One stage of a single repeating layer::

    {"arch": <registry name>, "r_max": 64, "layers_key": <depth key>,
     "targets": {<target>: [fan_out, fan_in], ...}}

or one entry per stage of the program's architecture, in its order::

    {"arch": ..., "r_max": 64, "stages": [
       {"depth": [<key>, <minus key>, ...],
        "targets": {<target>: [fan_out, fan_in], ...},
        "lead": {<target>: [<axis key>, ...]}}, ...]}

A stage's depth, in layers, is the file's value of its first depth key
less those of the others (``["num_hidden_layers",
"first_k_dense_replace"]`` is the depth after a dense prefix); it must be
a whole number of repeats of the stage's layer pattern.  Its published
depth is the same sum over the published values
(``reduced[key]["published"]`` where the key is cut).  ``targets`` are
keyed by the program's target name (the last part of the pair's path)
and hold the widths of that stage alone: two stages that share a target
name state its widths twice.  ``lead`` names, for a target with axes of
its own between the layer axis and the rank axis (the experts held), the
keys whose values are those axes.  With ``n`` the stage's repeats, every
pair must be A ``(n, *axes, r_max, fan_in)``, B ``(n, *axes, fan_out,
r_max)``, rank ``(n,)``.  ``program_overrides`` (optional) are set on
the program's architecture before it is built.

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
    r_max: int
    #: path -> (fan_out, fan_in, lead) in tree order; ``lead`` is the
    #: stage's repeats of its layer pattern (its depth, for a pattern of
    #: one layer) followed by the pair's own axes (the experts held)
    pairs: dict
    makers: dict = dataclasses.field(default_factory=dict)

    def maker(self, codec: str):
        """The jitted one-upload generator for ``codec`` (traced once)."""
        if codec not in self.makers:
            def upload(key, rank):
                return _client(self, codec, key, rank)
            self.makers[codec] = jax.jit(upload)
        return self.makers[codec]


def config_stages(config: dict) -> list:
    """The adapter's stages in the form with ``stages``: the one-stage
    form becomes one stage of depth ``[layers_key]`` with no lead."""
    ad = config["adapter"]
    if "stages" in ad:
        return ad["stages"]
    return [{"depth": [ad["layers_key"]], "targets": ad["targets"]}]


def stage_depth(depth_keys, values) -> int:
    """The first key's value less the others' (``values``: key -> int)."""
    first, *minus = depth_keys
    return int(values[first]) - sum(int(values[k]) for k in minus)


def program_layout(config: dict) -> Layout:
    """Build the client adapter layout through the program's public
    ``Model.init_adapters`` (shapes only) over every stage the
    configuration keeps, each at its stated depth, and assert every pair
    against the configuration file: ``A (*lead, r_max, fan_in)``, ``B
    (*lead, fan_out, r_max)``, ``rank (lead[0],)``, one pair per listed
    target of each stage and no other."""
    from repro.configs import get_config
    from repro.configs.base import Stage
    from repro.models.model import make_model

    ad = config["adapter"]
    r_max = int(ad["r_max"])
    stages = config_stages(config)
    arch = get_config(ad["arch"])
    if len(stages) != len(arch.stages):
        raise ValueError(f"the configuration states {len(stages)} stages, "
                         f"{ad['arch']} has {len(arch.stages)}")
    depths = [stage_depth(st["depth"], config) for st in stages]
    periods = [len(s.unit) for s in arch.stages]
    if any(d < p or d % p for d, p in zip(depths, periods)):
        raise ValueError(f"stage depths {depths} are not whole periods of "
                         f"{periods} layers")
    repeats = [d // p for d, p in zip(depths, periods)]
    arch = dataclasses.replace(
        arch, stages=tuple(Stage(unit=s.unit, repeat=n)
                           for s, n in zip(arch.stages, repeats)),
        lora_r_max=r_max, **config.get("program_overrides", {}))
    template = jax.eval_shape(
        lambda k: make_model(arch).init_adapters(k, r_max=r_max),
        jax.random.PRNGKey(0))
    pairs, seen = {}, [set() for _ in stages]
    for path, pair in pair_list(template):
        i = path[1] if path[0] == "stages" else None
        target = path[-1]
        if i is None or target not in stages[i]["targets"]:
            raise ValueError(f"program adapts {path}, which the "
                             "configuration file does not list")
        fo, fi = (int(w) for w in stages[i]["targets"][target])
        axes = stages[i].get("lead", {}).get(target, [])
        missing = [k for k in axes if k not in config]
        if missing:
            raise ValueError(f"pair {path}: lead keys {missing} are not "
                             "in the configuration file")
        lead = (repeats[i], *(int(config[k]) for k in axes))
        want = {"A": (*lead, r_max, fi), "B": (*lead, fo, r_max),
                "rank": (repeats[i],)}
        got = {k: tuple(pair[k].shape) for k in want}
        if got != want:
            raise ValueError(f"pair {path}: program shapes {got} differ "
                             f"from the configuration's {want}")
        pairs[path] = (fo, fi, lead)
        seen[i].add(target)
    for i, st in enumerate(stages):
        if seen[i] != set(st["targets"]):
            raise ValueError(f"stage {i}: program adapts {sorted(seen[i])}, "
                             f"configuration lists {sorted(st['targets'])}")
    return Layout(template=template, r_max=r_max, pairs=pairs)


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
    padding zero, as a trained and masked LoRA pair would be.  Each
    pair's stream is keyed by its index in tree order."""
    r = layout.r_max
    live = jnp.arange(r) < rank
    index = {p: i for i, p in enumerate(layout.pairs)}

    def make(path, pair):
        k = jax.random.fold_in(key, index[path])
        ka, kb, ksa, ksb = jax.random.split(k, 4)
        fo, fi, lead = layout.pairs[path]
        out = {"rank": jnp.full(lead[:1], rank, jnp.int32)}
        for side, kx, ks, shape, mask in (
                ("A", ka, ksa, (*lead, r, fi), live[:, None]),
                ("B", kb, ksb, (*lead, fo, r), live[None, :])):
            if codec == "int8":
                q = jax.random.randint(kx, shape, -127, 128, jnp.int8)
                out[side] = jnp.where(mask, q, 0).astype(jnp.int8)
                # one scale per row of every layer and expert, the
                # codec's (*lead, r) plane
                s = jax.random.uniform(ks, (*lead, r), jnp.float32, 1e-4,
                                       1e-3)
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
