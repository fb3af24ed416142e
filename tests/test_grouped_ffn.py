"""``ops/pallas/grouped_ffn.py`` (the routed experts' grouped GEMMs with the
weights fetched a group ahead and the activation inside) in interpret mode
against ``lax.ragged_dot`` and the product in plain jnp, and the rule by
which ``MoEExperts`` takes it: a call's pairs over the experts the graph's
ROUTER scores, a row tile or more.

Small contractions, the real row counts: the loads are a prompt chunk's
(8192 sorted pairs on 64 experts), so the metadata — active tiles, a tile
two groups share, the visited group after this one — is the timed cell's.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.core.op import OpContext
from flexflow_tpu.ops.pallas.grouped_ffn import grouped_ffn, out_tile
from flexflow_tpu.serve.ssd_moe_ops import GMM_ROWS, MoEExperts


def skewed(rng, e=64, visited=36, fullest=900, pairs=8076):
    """A chunk and layer of ``mellum2-d8.repo-context`` under its seeded
    draw: 36 of 64 experts visited, the fullest 900 of 8076 pairs."""
    rest = rng.multinomial(pairs - fullest - (visited - 1),
                           np.full(visited - 1, 1 / (visited - 1))) + 1
    sizes = np.zeros(e, np.int64)
    sizes[np.sort(rng.choice(e, visited, replace=False))] = rng.permutation(
        np.concatenate([[fullest], rest]))
    assert sizes.sum() == pairs and sizes.max() == fullest
    return sizes


def plain(x, weights, sizes, form):
    """The oracle: ``lax.ragged_dot`` a matrix, the activation in jnp."""
    dot = lambda w: jax.lax.ragged_dot(x, w, sizes,
                                       preferred_element_type=jnp.float32)
    if form == "swiglu":
        return jax.nn.silu(dot(weights[0])) * dot(weights[1])
    if form == "relu2":
        return jnp.square(jnp.maximum(dot(weights[0]), 0.0))
    return dot(weights[0])


RNG = np.random.default_rng(62)
CASES = {
    # name: (rows, contraction, columns, sizes, form, tm, tn, dtype)
    "balanced_64x128": (8192, 128, 256, np.full(64, 128), "swiglu", 128,
                        None, jnp.float32),
    "skew_900_of_8076": (8192, 128, 128, skewed(RNG), "swiglu", 128, None,
                         jnp.float32),
    "skew_relu2": (8192, 128, 128, skewed(RNG), "relu2", 128, None,
                   jnp.float32),
    "skew_down": (8192, 128, 256, skewed(RNG), "linear", 128, None,
                  jnp.float32),
    # the last group ends inside a tile; 212 pairs of absent experts behind
    "short_of_m": (512, 128, 128, np.array([0, 130, 0, 37, 132, 0, 1, 0]),
                   "swiglu", 128, None, jnp.float32),
    "short_of_m_relu2": (512, 128, 128, np.array([7, 0, 0, 250, 0, 0, 0, 3]),
                         "relu2", 128, None, jnp.float32),
    # deepseek_v2's 1408 = 11 x 128 in tiles of 768: the second is 640 wide
    "ragged_1408": (512, 128, 1408, np.array([100, 0, 156, 200]), "swiglu",
                    128, 768, jnp.float32),
    "ragged_1408_relu2": (512, 128, 1408, np.array([0, 300, 0, 212]),
                          "relu2", 128, 768, jnp.float32),
    "column_tiles_one_group": (256, 128, 512, np.array([0, 200, 0, 0]),
                               "linear", 128, 128, jnp.float32),
    "row_tile_256": (1024, 128, 256, np.array([300, 0, 5, 400, 319, 0]),
                     "swiglu", 256, None, jnp.float32),
    "row_tile_64_two_places": (512, 128, 256, np.array([0, 300, 0, 212]),
                               "swiglu", 64, None, jnp.float32),
    "column_tiles_skew": (8192, 128, 256, skewed(RNG), "linear", 128, 128,
                          jnp.float32),
    "bf16_one_cast": (1024, 256, 256, np.array([300, 0, 5, 400, 319, 0]),
                      "swiglu", 128, None, jnp.bfloat16),
}


@pytest.mark.parametrize("case", list(CASES))
def test_grouped_ffn_matches_ragged_dot(case):
    m, k, n, sizes, form, tm, tn, dtype = CASES[case]
    rng = np.random.default_rng(len(case))
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape) * 0.3,
                                      dtype)
    e = len(sizes)
    x = draw(m, k)
    weights = tuple(draw(e, k, n) for _ in range(2 if form == "swiglu"
                                                 else 1))
    sizes = jnp.asarray(sizes, jnp.int32)
    got = grouped_ffn(x, weights, sizes, form=form, out_dtype=dtype, tm=tm,
                      tn=tn, interpret=True)
    assert got.shape == (m, n) and got.dtype == dtype
    live = int(sizes.sum())         # rows past the groups' sum: whatever
    want = plain(x, weights, sizes, form)[:live]
    got = np.asarray(got[:live], np.float32)
    if dtype == jnp.bfloat16:
        # the same float32 products and ONE cast: an ulp of bf16 apart where
        # the sums' order put the float32 on either side of a rounding
        want = np.asarray(want.astype(jnp.bfloat16), np.float32)
        np.testing.assert_allclose(got, want, rtol=2 ** -7, atol=1e-6)
        assert (got == want).mean() > 0.98
    else:
        np.testing.assert_allclose(got, np.asarray(want), rtol=2e-5,
                                   atol=2e-5)


def test_out_tile_is_whole_where_two_slots_fit():
    # mellum's 2304 x 896 either way: whole; cohere's 4096 x 4096: gate and
    # up in 4 column tiles of 1024 (two slots of two 8 MB blocks), down in 2
    assert out_tile(128, 2304, 896, 2, 2, 2) == 896
    assert out_tile(128, 896, 2304, 2, 4, 1) == 2304
    assert out_tile(128, 4096, 4096, 2, 2, 2) == 1024
    assert out_tile(128, 4096, 4096, 2, 4, 1) == 2048


def _experts(op, rows, interp=True):
    """``op`` lowered on ``rows`` sorted pairs with the kernels on: the
    jitted function, its operands, and the path it noted."""
    e, d, f = op.num_held, op.embed_dim, op.width
    paths = {}

    def layer(xs, sizes, gate, up, down):
        ctx = OpContext(extras={"node_name": "n", "pallas_decode": True,
                                "pallas_interpret": interp,
                                "attention_paths": paths})
        return op.lower(ctx, [xs, sizes],
                        {"gate": gate, "up": up, "down": down})[0]

    sds = jax.ShapeDtypeStruct
    operands = (sds((rows, d), jnp.float32), sds((e,), jnp.int32),
                sds((e, d, f), jnp.float32), sds((e, d, f), jnp.float32),
                sds((e, f, d), jnp.float32))
    return layer, operands, paths


def _as_it_stood(op, interp=True):
    """``MoEExperts.lower`` on megablox as PR 61 left it."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    def layer(xs, sizes, gate, up, down):
        grouped = lambda a, w, tile: gmm(a, w, sizes, jnp.float32,
                                         (GMM_ROWS, w.shape[1], tile),
                                         interpret=interp)
        hidden = op.out_tile(op.embed_dim, op.width, 4)
        model = op.out_tile(op.width, op.embed_dim, 4)
        h = grouped(xs, up, hidden)
        h = jax.nn.silu(grouped(xs, gate, hidden)) * h
        return grouped(h.astype(xs.dtype), down, model)

    return layer


@pytest.mark.parametrize("held, scored, rows, path", [
    # mellum2-d8: all 64 held; a chunk of 1024 rows x top-8, a scan of 16
    (64, 64, 8192, "grouped_ffn"), (64, 64, 128, "megablox_gmm"),
    # command-a-plus-d4-e16: 16 of 128 held; M / num_held = 256, but a chunk
    # of 512 rows x top-8 brings 4096 / 128 = 32 rows an expert
    (16, 128, 4096, "megablox_gmm"),
], ids=["mellum_chunk", "mellum_scan", "held_16_of_128_chunk"])
def test_the_routers_width_picks_the_plan(held, scored, rows, path):
    op = MoEExperts(held, 128, 128, form="swiglu", num_scored=scored)
    assert op.group_ahead(rows) == (path == "grouped_ffn")
    layer, operands, paths = _experts(op, rows)
    text = jax.jit(layer).lower(*operands).as_text()
    assert paths == {("moe_experts", "NoneType"): path}
    same = text == jax.jit(_as_it_stood(op)).lower(*operands).as_text()
    # below the rule: the HLO it lowered to before there was a rule
    assert same == (path == "megablox_gmm")


def test_widths_that_are_not_whole_lanes_stay_on_megablox():
    # nemotron_h's 1856 = 14.5 x 128: the plan's copies move whole tiles
    assert not MoEExperts(64, 2688, 1856, num_scored=64).group_ahead(8192)
    assert MoEExperts(64, 2304, 896, num_scored=64).group_ahead(8192)
    assert MoEExperts(64, 2304, 896).num_scored == 64


def test_both_plans_give_the_oracles_rows():
    """A chunk through ``MoEExperts`` on the group-ahead plan, on megablox
    (the same graph told its router scores four times the experts) and on
    ``lax.ragged_dot`` (kernels off): the same rows."""
    rng = np.random.default_rng(7)
    e, d, f, rows = 4, 128, 128, 512
    sizes = jnp.asarray([200, 0, 37, 150], jnp.int32)
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape) * 0.3,
                                      jnp.float32)
    args = (draw(rows, d), sizes, draw(e, d, f), draw(e, d, f),
            draw(e, f, d))
    outs = {}
    for scored in (e, 4 * e):
        layer, _, paths = _experts(MoEExperts(e, d, f, form="swiglu",
                                              num_scored=scored), rows)
        outs[scored] = np.asarray(layer(*args))[:387]
        assert set(paths.values()) == {
            "grouped_ffn" if scored == e else "megablox_gmm"}
    off = MoEExperts(e, d, f, form="swiglu").lower(
        OpContext(extras={"node_name": "n"}), list(args[:2]),
        dict(zip(("gate", "up", "down"), args[2:])))[0]
    for got in outs.values():
        np.testing.assert_allclose(got, np.asarray(off)[:387], rtol=2e-5,
                                   atol=2e-5)
