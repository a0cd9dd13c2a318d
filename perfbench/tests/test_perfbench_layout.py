"""The adapter layout, the work functions and the reference over every
stage and leading axis, on the CPU.  The two committed configurations
keep the tree, the uploads and the work they had when the layout held
one stage of dense pairs (the digests and integers below were computed
from that layout); a tree with an expert axis is checked pair by pair
against its file, and the reference over that axis equals a loop over
its experts."""
import hashlib
import json
import math
import re
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import compare  # noqa: E402
import gen  # noqa: E402
import harness  # noqa: E402
import work  # noqa: E402
from tiny_configs import TINY, TINY_GLM  # noqa: E402

SEED = 2 ** 33 + 17
ORDER = ["ffn/down", "ffn/gate", "ffn/up", "mix/k", "mix/o", "mix/q",
         "mix/v"]


def config(name):
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


def sizing():
    """The DeepSeek-V3 one-chip share at its published widths."""
    return json.loads((Path(__file__).resolve().parent
                       / "deepseek-v3.l5e8.json").read_text())


# --------------------------------------------- the committed layouts ----
#: (fan_out, fan_in) per target in tree order, and the depth
COMMITTED = {
    "h2o-danube-3-4b.l6": (6, [(3840, 10240), (10240, 3840), (10240, 3840),
                               (960, 3840), (3840, 3840), (3840, 3840),
                               (960, 3840)]),
    "chatglm3-6b.l7": (7, [(4096, 13696), (13696, 4096), (13696, 4096),
                           (256, 4096), (4096, 4096), (4096, 4096),
                           (256, 4096)]),
}


@pytest.mark.parametrize("name", list(COMMITTED))
def test_committed_layouts_keep_their_pair_order_and_shapes(name):
    layers, widths = COMMITTED[name]
    lay = gen.program_layout(config(name))
    assert list(lay.pairs) == [("stages", 0, "b0", t) for t in ORDER]
    for (path, pair), (fo, fi) in zip(gen.pair_list(lay.template), widths,
                                      strict=True):
        assert lay.pairs[path] == (fo, fi, (layers,))
        assert pair["A"].shape == (layers, 64, fi)
        assert pair["B"].shape == (layers, fo, 64)
        assert pair["rank"].shape == (layers,)


def digest(tree) -> str:
    import jax
    import numpy as np
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)}:{a.dtype}:{a.shape}"
                 .encode())
        h.update(a.tobytes())
    return h.hexdigest()


FIRST_UPLOAD = {
    ("tiny", "none"): "7f3197b4ccbe165e282b343a982235c7"
                      "66857d45268748153d8ccdb122e73e4c",
    ("tiny", "int8"): "720913a085c1d0f0b1403492540c5364"
                      "a5ce195f9ec568b488d0fa6e551e704a",
    ("tiny_glm", "none"): "5e53af27d9a19c874e1a4d3d16295d60"
                          "99d8aa6409c3de63069aa72cb81a54d1",
    ("tiny_glm", "int8"): "0f57a4e9c69cbe0e2119ba3c99ace9b7"
                          "fe4528ce78b67c87f48dfb1a03f54368",
}


@pytest.mark.parametrize("cut,codec", list(FIRST_UPLOAD))
def test_first_upload_is_the_same_bit_for_bit(cut, codec):
    lay = gen.program_layout({"tiny": TINY, "tiny_glm": TINY_GLM}[cut])
    up = gen.make_upload(lay, SEED, lay.r_max // 2, codec, 0, 0)
    assert digest(up) == FIRST_UPLOAD[cut, codec]


#: (bytes, flops) of a round of each sync mix, then of folding one
#: upload at ranks 8, 16, 32, 64 in f32 and in int8
WORK = {
    "h2o-danube-3-4b.l6": {
        "sync-rbla-f32.n32": (1135411200, 541900800),
        "sync-rbla-int8.n128": (1136271360, 3122380800),
        "sync-rbla-f32.n24-retain": (670924800, 322560000),
        "none": [(38712576, 9676800), (77425152, 19353600),
                 (154850304, 38707200), (309700608, 77414400)],
        "int8": [(29038464, 9676800), (58076928, 19353600),
                 (116153856, 38707200), (232307712, 77414400)]},
    "chatglm3-6b.l7": {
        "sync-rbla-f32.n32": (1546682368, 738189312),
        "sync-rbla-int8.n128": (1547685888, 4253376512),
        "sync-rbla-f32.n24-retain": (913948672, 439398400),
        "none": [(52734080, 13181952), (105468160, 26363904),
                 (210936320, 52727808), (421872640, 105455616)],
        "int8": [(39555264, 13181952), (79110528, 26363904),
                 (158221056, 52727808), (316442112, 105455616)]},
}


@pytest.mark.parametrize("name", list(WORK))
def test_work_is_the_same_to_the_integer(name):
    lay = gen.program_layout(config(name))
    want = WORK[name]
    for mix in ("sync-rbla-f32.n32", "sync-rbla-int8.n128",
                "sync-rbla-f32.n24-retain"):
        t = json.loads((HERE / "traffic" / f"{mix}.json").read_text())
        ranks = gen.pool_ranks(t, lay.r_max, t["cohort"])
        got = work.round_work(lay.pairs.values(), lay.r_max, ranks,
                              t["codec"])
        assert (got["bytes"], got["flops"]) == want[mix], mix
    for codec in ("none", "int8"):
        got = [work.fold_work(lay.pairs.values(), lay.r_max, rank, codec)
               for rank in (8, 16, 32, 64)]
        assert [(w["bytes"], w["flops"]) for w in got] == want[codec]


# ------------------------------------------ stages and an expert axis ----
def test_the_published_deepseek_v3_share_matches_the_program():
    """Shapes only: 1 dense and 4 MoE layers at published widths, 8
    experts held, 401.7 MB of f32 per client at r_max 64."""
    lay = gen.program_layout(sizing())
    assert len(lay.pairs) == 19
    stage = {p[1] for p in lay.pairs}
    assert stage == {0, 1}
    experts = {p: v for p, v in lay.pairs.items() if "experts" in p[-1]}
    assert sorted(p[-1] for p in experts) == [
        "ffn/experts/down", "ffn/experts/gate", "ffn/experts/up"]
    assert {v[2] for v in experts.values()} == {(4, 8)}
    assert lay.pairs["stages", 1, "b0", "mix/kv_a"] == (576, 7168, (4,))
    assert lay.pairs["stages", 0, "b0", "ffn/gate"] == (18432, 7168, (1,))
    nbytes = sum(64 * (fo + fi) * 4 * math.prod(lead)
                 for fo, fi, lead in lay.pairs.values())
    assert nbytes == 401686528


def test_work_counts_every_expert_row():
    """A pair with an expert axis needs E times the rows of one without."""
    dense = [(96, 64, (4,))]
    moe = [(96, 64, (4, 8))]
    for fn, args in ((work.round_work, ([2, 4, 8], "int8")),
                     (work.fold_work, (4, "int8"))):
        d, m = fn(dense, 8, *args), fn(moe, 8, *args)
        assert m == {k: 8 * v for k, v in d.items()}


def _variant(edit):
    cfg = sizing()
    edit(cfg["adapter"]["stages"])
    return cfg


@pytest.mark.parametrize("what,edit,path", [
    ("expert target omitted",
     lambda st: st[1]["targets"].pop("ffn/experts/down"),
     ("stages", 1, "b0", "ffn/experts/down")),
    ("expert axis not stated",
     lambda st: st[1]["lead"].pop("ffn/experts/up"),
     ("stages", 1, "b0", "ffn/experts/up")),
    ("wrong leading axis",
     lambda st: st[1]["lead"].update({"ffn/experts/gate":
                                      ["first_k_dense_replace"]}),
     ("stages", 1, "b0", "ffn/experts/gate")),
    ("a width misstated in one of two stages that share the name",
     lambda st: st[0]["targets"].update({"mix/o": [2048, 16384]}),
     ("stages", 0, "b0", "mix/o")),
])
def test_a_misstated_pair_is_refused_with_its_path(what, edit, path):
    with pytest.raises(ValueError, match=re.escape(str(path))):
        gen.program_layout(_variant(edit))


def test_a_stage_the_configuration_leaves_out_is_refused():
    with pytest.raises(ValueError, match="1 stages"):
        gen.program_layout(_variant(lambda st: st.pop()))


def _ref_side(xs, scales, ws, ranks, prev, side, dtype):
    rbla = harness.load_module("references", "rbla")
    return rbla.rbla_side(xs, scales, ws, ranks, prev, side=side,
                          dtype=dtype)


@pytest.mark.parametrize("side", ["A", "B"])
@pytest.mark.parametrize("int8", [False, True])
def test_reference_over_an_expert_axis_equals_a_loop_over_experts(side,
                                                                 int8):
    import jax
    import jax.numpy as jnp
    import numpy as np
    L, E, r, d, n = 4, 8, 8, 24, 5           # E != L
    shape = (L, E, r, d) if side == "A" else (L, E, d, r)
    keys = jax.random.split(jax.random.PRNGKey(7), 2 * n + 1)
    xs = [jax.random.normal(keys[i], shape) for i in range(n)]
    scales = ([jax.random.uniform(keys[n + i], (L, E, r), minval=0.1)
               for i in range(n)] if int8 else None)
    prev = jax.random.normal(keys[-1], shape)
    ws = jnp.asarray([50.0, 120.0, 300.0, 80.0, 410.0])
    ranks = jnp.asarray([1, 2, 4, 2, 6], jnp.int32)   # no client at r
    whole = _ref_side(xs, scales, ws, ranks, prev, side, jnp.float32)
    per_expert = jnp.stack([
        _ref_side([x[:, e] for x in xs],
                  None if scales is None else [s[:, e] for s in scales],
                  ws, ranks, prev[:, e], side, jnp.float32)
        for e in range(E)], axis=1)
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(per_expert))
    # rows no client owns keep the previous global
    top = (slice(None), slice(None), slice(6, None))
    if side == "B":
        top = (slice(None), slice(None), slice(None), slice(6, None))
    np.testing.assert_array_equal(np.asarray(whole[top]),
                                  np.asarray(prev[top]))


def test_judge_counts_rank_entries_of_any_shape():
    import jax.numpy as jnp
    got = {"a": {"A": jnp.ones((4, 8, 2, 3)), "B": jnp.ones((4, 8, 3, 2)),
                 "rank": jnp.asarray([2, 1, 2, 2])},
           "b": {"A": jnp.ones((2, 3)), "B": jnp.ones((3, 2)),
                 "rank": jnp.asarray(1)},
           "c": {"A": jnp.ones((2, 2, 2, 3)), "B": jnp.ones((2, 2, 3, 2)),
                 "rank": jnp.asarray([[2, 1], [0, 2]])}}
    want = [{"A": jnp.ones((4, 8, 2, 3)), "B": jnp.ones((4, 8, 3, 2))},
            {"A": jnp.ones((2, 3)), "B": jnp.ones((3, 2))},
            {"A": jnp.ones((2, 2, 2, 3)),
             "B": jnp.full((2, 2, 3, 2), 2.0)}]
    gap, off = compare.judge(got, want, 2)
    assert off == 1 + 1 + 2
    assert gap == pytest.approx(0.5)
