"""The three orders of ``models/experts.py::routed_experts``: the loop of
every held expert over every row, the pipelined pass that does the loop's
work in one kernel (ISSUE 44), and the grouped path that runs each held
expert over its own rows only (ISSUE 43; both kernels are
``ops/pallas/grouped_experts.py``, in interpret mode here), on the leaves of
the four expert blocks at toy widths; the static rules that choose between
them; what ``share`` counts."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import test_afmoe
import test_deepseek_v32
import test_granite_hybrid
import test_mimo_v2

from picotron_tpu.inference import InferenceEngine
from picotron_tpu.models import afmoe, deepseek_v32, experts, granite_hybrid
from picotron_tpu.models import mimo_v2
from picotron_tpu.ops.pallas import grouped_experts as grouped

TILE = grouped.TILE
# block -> (its module, its test file's toy configuration with five experts
# held of a router ten wide, the group of its tree that holds expert layers)
BLOCKS = {
    "granite_hybrid": (granite_hybrid, lambda **kw: test_granite_hybrid
                       .make_config(dict(num_local_experts=5, ep_size=2,
                                         **kw)), "mamba_0"),
    "deepseek_v32": (deepseek_v32, lambda **kw: test_deepseek_v32.make_config(
        dict(test_deepseek_v32.TOY, n_routed_experts=5, ep_size=2, n_group=1,
             topk_group=1, **kw)), "layers"),
    "afmoe": (afmoe, lambda **kw: test_afmoe.make_config(
        dict(num_experts=5, ep_size=2, **kw)), "moe_window_1"),
    "mimo_v2": (mimo_v2, lambda **kw: test_mimo_v2.make_config(
        dict(n_routed_experts=5, ep_size=2, **kw)), "moe_window_1"),
}
HELD = 5


@functools.lru_cache(maxsize=None)
def expert_group(block: str, dtype: str = "float32"):
    """(the block's module, its toy ModelConfig, the stacked leaves of a
    group of its expert layers: ``UNSLICED`` ones [layers, held, ...])."""
    module, make, group = BLOCKS[block]
    m = make(dtype=dtype).model
    tree = jax.jit(lambda k: module.init_params(k, m))(jax.random.PRNGKey(3))
    return module, m, tree[group]


def layer_leaves(group: dict, row: int) -> dict:
    """What the layer scan hands layer ``row``: its slice of every leaf but
    the expert stacks, which arrive whole beside ``row``."""
    lp = {n: (v if n in experts.UNSLICED else v[row])
          for n, v in group.items()}
    return {**lp, "row": jnp.asarray(row, jnp.int32)}


def reference(lp: dict, x, w_held):
    """float32 ``jax.numpy``, one expert after the other, no kernel."""
    f = lambda v: jnp.asarray(v, jnp.float32)
    x, row = f(x), int(lp["row"])
    y = jnp.zeros(x.shape, jnp.float32)
    for e in range(w_held.shape[1]):
        w1, w3, w2 = (f(lp[n][row, e]) for n in experts.UNSLICED)
        y += ((jax.nn.silu(x @ w1) * (x @ w3)) @ w2) * w_held[:, e:e + 1]
    return np.asarray(y)


def draw(case: str, rng) -> tuple:
    """(rows N, ``w_held`` [N, HELD] float32) of a case."""
    N = {"ragged": 400, "empty_expert": 384, "one_expert": 512,
         "dead_rows": 400, "bfloat16": 400, "chunks": 1100}[case]
    w = rng.uniform(0.05, 1.0, (N, HELD)) * (rng.uniform(
        0, 1, (N, HELD)) < 0.4)
    if case == "empty_expert":
        w[:, 1] = 0.0  # a held expert no row chose, between two that have rows
    if case == "one_expert":
        # the no-drop case: every row on one expert (4 tiles of it), none
        # on the four others
        w[:] = 0.0
        w[:, 3] = rng.uniform(0.05, 1.0, N)
    if case == "dead_rows":
        # a padded bucket's tail, and rows ``live`` masks, in the middle too
        w[300:] = 0.0
        w[100:140] = 0.0
    return N, jnp.asarray(w, jnp.float32)


def loop_alone(monkeypatch):
    """Both rules off: every row count runs the loop."""
    monkeypatch.setattr(experts, "takes_grouped", lambda rows: False)
    monkeypatch.setattr(experts, "takes_pipelined", lambda *shape: False)


def tiles_run(w_held) -> int:
    """Tiles the grouped path runs, ``GROUP_ROWS`` rows a call."""
    w = np.asarray(w_held)
    return sum(int(np.sum(-(-np.sum(w[c:c + experts.GROUP_ROWS] > 0, axis=0)
                            // TILE)))
               for c in range(0, len(w), experts.GROUP_ROWS))


@pytest.mark.parametrize("case", ["ragged", "empty_expert", "one_expert",
                                  "dead_rows", "bfloat16", "chunks"])
@pytest.mark.parametrize("block", sorted(BLOCKS))
def test_grouped_is_the_loop_and_the_reference(block, case, monkeypatch):
    """The share of each block's own leaves (stacks read at ``row``, the
    shared expert where the block has one), rows past the rule's threshold:
    grouped against the loop and the float32 reference; every assignment
    computed at any skew, rows of weight 0 given nothing; the counts."""
    dtype = "bfloat16" if case == "bfloat16" else "float32"
    _, m, group = expert_group(block, dtype)
    lp = layer_leaves(group, 1)
    rng = np.random.default_rng(sum(map(ord, block + case)))
    N, w_held = draw(case, rng)
    x = jnp.asarray(rng.standard_normal((N, m.hidden_size)), lp["w1"].dtype)
    assert experts.takes_grouped(N)
    share = lambda: jax.jit(lambda lp, x, w: (
        experts.share(lp, x, w), experts.routed_experts(x, w, lp)[0]))(
            lp, x, w_held)
    (y, counted), routed = share()
    assert y.dtype == x.dtype
    loop_alone(monkeypatch)
    (y_loop, counted_loop), routed_loop = share()

    want = reference(lp, x, w_held)
    scale = float(np.max(np.abs(want)))
    tol = 2e-2 if dtype == "bfloat16" else 1e-5
    assert routed.dtype == routed_loop.dtype == jnp.float32
    np.testing.assert_allclose(routed, want, atol=tol * scale)
    np.testing.assert_allclose(routed_loop, want, atol=tol * scale)
    y, y_loop = np.asarray(y, np.float32), np.asarray(y_loop, np.float32)
    np.testing.assert_allclose(y, y_loop,
                               atol=tol * float(np.max(np.abs(y_loop))))
    dead = ~np.asarray(w_held > 0).any(axis=1)
    assert not np.asarray(routed)[dead].any()
    assert case != "dead_rows" or dead[300:].all() and dead[100:140].all()

    names = experts.STAT_NAMES
    got, loop = (dict(zip(names, map(int, c)))
                 for c in (counted, counted_loop))
    assert got["moe_assignments"] == loop["moe_assignments"] == int(
        (w_held > 0).sum())
    assert got["moe_experts_hit"] == loop["moe_experts_hit"]
    assert got["moe_layer_steps"] == loop["moe_layer_steps"] == 1
    assert loop["moe_expert_rows"] == N * HELD
    assert got["moe_expert_rows"] == tiles_run(w_held) * TILE
    if case == "one_expert":
        assert got["moe_experts_hit"] == 1
        assert got["moe_expert_rows"] == N == got["moe_assignments"]


@pytest.mark.parametrize("rows", [8, 16, 64, 100, 128, 200])
@pytest.mark.parametrize("stacked", [True, False], ids=["row", "sliced"])
@pytest.mark.parametrize("block", sorted(BLOCKS))
def test_pipelined_is_the_loop(block, stacked, rows, monkeypatch):
    """Rows below the ridge (a decode block's, a verify's, the buckets under
    240; counts that are not whole sublane tiles too) through the pipelined
    pass, the leaves a group's stacks read at ``row`` or one layer's own:
    the loop's sum to bfloat16's rounding, rows x held expert-rows counted,
    exact 0 for a row that weighs 0 everywhere, and EVERY held expert's
    matrices asked for, an expert no row chose among them (the weights'
    ``index_map`` over the whole grid: it sees the layer's row and the
    step, never the router's choice)."""
    _, m, group = expert_group(block, "bfloat16")
    lp = layer_leaves(group, 1)
    if not stacked:
        lp = {n: (v[1] if n in experts.UNSLICED else v)
              for n, v in lp.items() if n != "row"}
    rng = np.random.default_rng(rows + sum(map(ord, block)))
    w = rng.uniform(0.05, 1.0, (rows, HELD)) * (
        rng.uniform(0, 1, (rows, HELD)) < 0.4)
    w[:, 2] = 0.0  # a held expert no row chose
    w[rows // 2] = 0.0  # a slot that is not live
    w[-1] = 0.0
    w_held = jnp.asarray(w, jnp.float32)
    x = jnp.asarray(rng.standard_normal((rows, m.hidden_size)), jnp.bfloat16)
    assert not experts.takes_grouped(rows) and experts.takes_pipelined(
        rows, m.hidden_size, lp["w1"].shape[-1], 2)

    calls = []
    real = grouped.pl.pallas_call

    def spy(kernel, *args, **kw):
        calls.append(kw)
        return real(kernel, *args, **kw)

    monkeypatch.setattr(grouped.pl, "pallas_call", spy)
    share = lambda: jax.jit(lambda lp, x, w: (
        experts.share(lp, x, w), experts.routed_experts(x, w, lp)[0]))(
            lp, x, w_held)
    (y, counted), routed = share()
    assert calls and all(kw["name"] == "pipelined_experts" for kw in calls)
    spec = calls[0]["grid_spec"]
    assert spec.grid == (HELD,)
    layer = 1 if stacked else 0
    for stack in spec.in_specs[2:]:
        assert [tuple(int(i) for i in stack.index_map(e, [layer]))
                for e in range(HELD)] == [(layer, e, 0, 0)
                                          for e in range(HELD)]
    del calls[:]
    loop_alone(monkeypatch)
    (y_loop, counted_loop), routed_loop = share()
    assert not calls

    stacks = lp if stacked else {
        **{n: lp[n][None] for n in experts.UNSLICED}, "row": 0}
    want = reference(stacks, x, w_held)
    scale = float(np.max(np.abs(want)))
    assert y.dtype == x.dtype and routed.dtype == jnp.float32
    assert routed.shape == x.shape
    np.testing.assert_allclose(routed, want, atol=2e-2 * scale)
    np.testing.assert_allclose(routed, routed_loop, atol=2e-2 * scale)
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(y_loop, np.float32),
        atol=2e-2 * float(np.max(np.abs(np.asarray(y_loop, np.float32)))))
    dead = ~(w > 0).any(axis=1)
    assert dead[rows // 2] and dead[-1]
    assert not np.asarray(routed)[dead].any()
    got, loop = (dict(zip(experts.STAT_NAMES, map(int, c)))
                 for c in (counted, counted_loop))
    assert got["moe_expert_rows"] == loop["moe_expert_rows"] == rows * HELD
    assert got["moe_pipelined_steps"] == 1 and loop["moe_pipelined_steps"] == 0
    assert got["moe_layer_steps"] == loop["moe_layer_steps"] == 1
    assert got["moe_experts_hit"] == loop["moe_experts_hit"] < HELD
    assert got["moe_assignments"] == loop["moe_assignments"] == int(
        (w > 0).sum())


def test_an_expert_no_row_chose_is_run_all_the_same():
    """What a step reads must not follow the seed's router: a NaN planted in
    the ``w2`` of a held expert that weighs 0 on every row reaches the sum
    through the pipelined pass as it does through the loop (0 x NaN), so
    the expert's matrices were fetched and multiplied, not skipped."""
    _, m, group = expert_group("granite_hybrid", "bfloat16")
    lp = layer_leaves(group, 0)
    rng = np.random.default_rng(2)
    w = rng.uniform(0.05, 1.0, (16, HELD))
    w[:, 3] = 0.0
    x = jnp.asarray(rng.standard_normal((16, m.hidden_size)), jnp.bfloat16)
    routed = lambda lp: experts.routed_experts(x, jnp.asarray(
        w, jnp.float32), lp)
    y, _, pipelined = routed(lp)
    assert int(pipelined) == 1 and np.isfinite(np.asarray(y)).all()
    y, _, pipelined = routed(
        {**lp, "w2": lp["w2"].at[0, 3, 0, 0].set(jnp.nan)})
    assert int(pipelined) == 1 and np.isnan(np.asarray(y)).any()


@pytest.mark.parametrize("block", sorted(BLOCKS))
def test_a_blocks_own_router_through_both_orders(block, monkeypatch):
    """``expert_mlp`` as a layer calls it, the block's router choosing, a
    bucket's padded tail not live: the two orders agree and count alike."""
    module, m, group = expert_group(block)
    lp = layer_leaves(group, 0)
    x = jax.random.normal(jax.random.PRNGKey(9), (1, 400, m.hidden_size))
    live = (jnp.arange(400) < 330)[None]
    mlp = lambda: jax.jit(lambda lp, x, live: module.expert_mlp(
        lp, x, m, live))(lp, x, live)
    y, counted = mlp()
    loop_alone(monkeypatch)
    y_loop, counted_loop = mlp()
    np.testing.assert_allclose(y, y_loop, atol=1e-5 * float(
        jnp.max(jnp.abs(y_loop))))
    got, loop = (dict(zip(experts.STAT_NAMES, map(int, c)))
                 for c in (counted, counted_loop))
    assert 0 < got["moe_assignments"] == loop["moe_assignments"] \
        <= 330 * min(HELD, len(got))
    assert loop["moe_expert_rows"] == 400 * HELD
    assert got["moe_assignments"] <= got["moe_expert_rows"] \
        < loop["moe_expert_rows"]


def test_a_weight_block_of_part_of_the_width(monkeypatch):
    """The expert's width in blocks (DeepSeek's 2,048 at 256 a step): the
    float32 accumulator carries a tile from block to block."""
    rng = np.random.default_rng(5)
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape) * 0.1,
                                   jnp.float32)
    lp = {"w1": f(2, HELD, 64, 512), "w3": f(2, HELD, 64, 512),
          "w2": f(2, HELD, 512, 64), "row": jnp.asarray(1, jnp.int32)}
    N, w_held = draw("ragged", rng)
    x = f(N, 64) * 10
    want = reference(lp, x, w_held)
    # room for two buffers of three blocks 128 wide: four steps a tile
    monkeypatch.setattr(grouped, "WEIGHT_VMEM", 3 * 64 * 128 * 4 * 2)
    assert grouped._block_i(64, 512, 4) == 128
    routed = experts.routed_experts(x, w_held, lp)[0]
    np.testing.assert_allclose(routed, want,
                               atol=1e-5 * float(np.max(np.abs(want))))
    # below the ridge such an expert keeps the loop: the pass takes whole
    # matrices only
    assert not experts.takes_pipelined(100, 64, 512, 4)
    routed, run, pipelined = experts.routed_experts(x[:100], w_held[:100], lp)
    assert int(pipelined) == 0 and int(run) == 100 * HELD
    np.testing.assert_allclose(routed, want[:100],
                               atol=1e-5 * float(np.max(np.abs(want))))
    monkeypatch.undo()
    assert experts.takes_pipelined(100, 64, 512, 4)
    # Granite's 768 goes whole, DeepSeek's 2,048 in eight, at the budget
    assert grouped._block_i(4096, 768, 2) == 768
    assert grouped._block_i(7168, 2048, 2) == 256
    assert grouped._block_i(3072, 3072, 2) == 768
    assert grouped._block_i(64, 32, 4) == 32


def test_tiles_hold_every_assignment_of_any_routing():
    """``group_rows``: ranks by expert, a tile one expert's, the last of a
    group padded; the static tile count holds the worst routing."""
    rng = np.random.default_rng(0)
    w = rng.uniform(0, 1, (300, 4)) * (rng.uniform(0, 1, (300, 4)) < 0.5)
    w[:, 2] = 0.0
    w[:, 3] = 0.7  # every row
    rank, expert, base, tiles = experts.group_rows(jnp.asarray(w, jnp.float32),
                                                   TILE)
    counts = (w > 0).sum(axis=0)
    per = -(-counts // TILE)
    assert int(tiles) == per.sum() and per[3] == 3 and per[2] == 0
    assert expert.shape == base.shape == (4 * 3,)  # held x ceil(300 / 128)
    want_expert = np.repeat(np.arange(4), per)
    want_base = np.concatenate([np.arange(n) * TILE for n in per])
    run = int(tiles)
    assert np.asarray(expert)[:run].tolist() == want_expert.tolist()
    assert np.asarray(base)[:run].tolist() == want_base.tolist()
    # past the count: the last tile that runs, again
    assert set(np.asarray(expert)[run:]) == {want_expert[-1]}
    assert set(np.asarray(base)[run:]) == {want_base[-1]}
    rank = np.asarray(rank)
    for e in range(4):
        mine = rank[:, e][w[:, e] > 0]
        assert mine.tolist() == list(range(counts[e]))
        assert (rank[:, e][w[:, e] == 0] == -1).all()
    # all of it on every expert fills the static bound exactly; none of it
    # runs nothing and names a tile that exists
    full = experts.group_rows(jnp.ones((300, 4), jnp.float32), TILE)
    assert int(full[3]) == full[1].shape[0] == 12
    none = experts.group_rows(jnp.zeros((300, 4), jnp.float32), TILE)
    assert int(none[3]) == 0 and set(np.asarray(none[1])) <= set(range(4))


def scopes(lowered) -> str:
    return lowered.as_text(debug_info=True)


# the four expert configurations' published (hidden size, expert width)
PUBLISHED = {"granite": (4096, 768), "deepseek": (7168, 2048),
             "trinity": (3072, 3072), "mimo": (4096, 2048)}


def toy_granite_engine():
    cfg = test_granite_hybrid.make_config(
        dict(num_local_experts=6, ep_size=2, num_experts_per_tok=4,
             max_position_embeddings=1024), training={"seq_length": 1024})
    engine = InferenceEngine(cfg, slots=4, max_seq_len=1024,
                             prefill_chunk=512)
    params = engine.shard_params(jax.jit(
        lambda k: granite_hybrid.init_params(k, cfg.model))(
            jax.random.PRNGKey(7)))
    return cfg, engine, params


def lowered_block(engine, params, cache):
    return engine._program("decode_block").lower(
        params, cache, jnp.zeros((6, 4), jnp.int32),
        jnp.zeros((engine.decode_block_len, 2), jnp.uint32))


def test_the_rule_is_of_the_shapes_alone(monkeypatch):
    """From the ridge up the rows go grouped; below it (a decode block's
    rows, a verify's, the buckets up to 128) they take the pipelined pass
    where an expert's three matrices go whole through the kernel's weight
    budget, which of the four published shapes is Granite's alone (MiMo's
    and Trinity's were tried a block of their width at a time and taken
    back out on their cells' readings: PERF.md section 6, PR 44), and the
    loop where they do not: in the Granite engine's own programs (the scopes
    the two kernels' paths open under ``moe_experts``), and in what the
    counters read."""
    assert [experts.takes_grouped(n) for n in (8, 64, 128, 256, 384, 512)] \
        == [False, False, False, True, True, True]
    for rows in (8, 16, 32, 64, 128, 239, 240, 512):
        assert {name: experts.takes_pipelined(rows, H, I, 2)
                for name, (H, I) in PUBLISHED.items()} == {
            "granite": rows < 240, "deepseek": False, "trinity": False,
            "mimo": False}, rows
    # float32 weights are twice the bytes: Granite's no longer go whole
    assert not experts.takes_pipelined(64, 4096, 768, 4)
    assert experts.takes_pipelined(64, 4096, 384, 4)
    assert experts.takes_pipelined(8, 64, 32, 4)  # the toy blocks here

    cfg, engine, params = toy_granite_engine()
    cache = engine.init_cache()
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)
    block = lowered_block(engine, params, cache)
    bucket = engine._prefill_jit.lower(params, i32(1, 128), i32(1) + 100)
    chunk = engine._prefill_chunk_jit.lower(params, cache, i32(1, 512),
                                            i32(), i32(), i32() + 512)
    for below in (block, bucket):
        assert "moe_experts/pipelined" in scopes(below)
        assert "moe_experts/grouped" not in scopes(below)
    assert "moe_experts/grouped" in scopes(chunk)
    assert "moe_experts/pipelined" not in scopes(chunk)

    # a seeded prompt of 700 tokens: a 512-row chunk grouped, then 188 rows
    # (and 324 pad rows, routed nowhere) grouped too; four decode steps of
    # one live slot of four through the pipelined pass
    prompt = np.random.default_rng(1).integers(1, 256, 700)
    cache, _ = engine.prefill_chunked(params, cache, prompt, 0)
    stats = dict(zip(granite_hybrid.STAT_NAMES, engine.take_stats()))
    layers = cfg.model.num_hidden_layers
    assert stats["moe_layer_steps"] == 2 * layers
    assert stats["moe_pipelined_steps"] == 0
    # about half of 700 x 4 choices land on this half of the router
    assert 0.35 * 2800 * layers < stats["moe_assignments"] \
        < 0.65 * 2800 * layers
    # the loop would have run 2 x 512 x 6 rows a layer; grouped, each of the
    # six held experts' ~230 and ~63 rows round up to whole tiles
    assert stats["moe_expert_rows"] % TILE == 0
    ratio = stats["moe_expert_rows"] / stats["moe_assignments"]
    assert 1.0 <= ratio < 2.0 < 2 * 512 * 6 * layers / stats["moe_assignments"]
    for _ in range(4):
        cache, _ = test_granite_hybrid.decode(engine, params, cache, 5)[:2]
    stats = dict(zip(granite_hybrid.STAT_NAMES, engine.take_stats()))
    assert stats["moe_layer_steps"] == 4 * layers
    assert stats["moe_pipelined_steps"] == 4 * layers
    assert stats["moe_expert_rows"] == 4 * layers * 4 * 6  # rows x held

    # an expert too wide for the budget: the same engine's decode block
    # keeps the loop, and counts no pipelined step
    monkeypatch.setattr(grouped, "WEIGHT_VMEM", 1 << 10)
    cfg, engine, params = toy_granite_engine()
    cache = engine.init_cache()
    block = lowered_block(engine, params, cache)
    assert "moe_experts" in scopes(block)
    assert "moe_experts/pipelined" not in scopes(block)
    assert "moe_experts/grouped" not in scopes(block)
    cache, _ = test_granite_hybrid.decode(engine, params, cache, 5)[:2]
    stats = dict(zip(granite_hybrid.STAT_NAMES, engine.take_stats()))
    assert stats["moe_layer_steps"] == layers
    assert stats["moe_pipelined_steps"] == 0
    assert stats["moe_expert_rows"] == layers * 4 * 6
