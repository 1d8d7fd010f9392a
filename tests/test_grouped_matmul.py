"""The grouped way's three products through one row-tiled kernel
(ISSUE 64): ``ops/pallas/grouped_matmul.py`` interpreted against
``jax.lax.ragged_dot`` on the same operands, its table of visits, the
rule that engages it by shape (``ops/expert_ffn.py:grouped_path``) with
its counter, and ``held_experts_part`` whole through it — forward and
backward. Shapes stay tiny: the interpreter is slow."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import expert_ffn
from paddle_tpu.ops import pallas as pk
from paddle_tpu.ops.pallas import grouped_matmul as gm

F32 = jnp.float32
# buffer rows, contraction, columns, groups; row tiles of 128
R, K, N, E, TM = 512, 128, 256, 5, 128

# rows of each group, from row 0
DRAWS = {
    # boundaries off the row tile, an empty group, rows past the last
    # group (397 of 512 live)
    "off_the_tile": [100, 0, 200, 37, 60],
    # a group larger than a tile (three of them, whole), then one row
    "over_a_tile": [384, 0, 0, 1, 0],
    # one row a group: five visits of one tile
    "one_row_each": [1, 1, 1, 1, 1],
    # empty groups first and between, the buffer full to its last row
    "full_buffer": [0, 300, 0, 0, 212],
    "every_row_dead": [0, 0, 0, 0, 0],
}


def _operands(dtype, seed=0):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(R, K), dtype)
    w_gate, w_up = (jnp.asarray(rng.randn(E, K, N) * 0.1, dtype)
                    for _ in range(2))
    return x, w_gate, w_up


def _tol(dtype, want):
    return (1e-5 if dtype == F32 else 2.0 ** -7) * float(
        np.abs(want).max(initial=1e-6))


@pytest.mark.parametrize("dtype", [F32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("draw", sorted(DRAWS))
def test_the_kernel_gives_ragged_dots_rows(draw, dtype):
    """Every live row is ``ragged_dot``'s, in float32 from products in
    the storage dtype; the rows past the last group are the caller's
    not to read — with NaN weights in every expert that has no row, and
    NaN in the dead rows' inputs, the live rows stay finite: a dead
    tile does no product and an empty group's weights are not read."""
    x, w, _ = _operands(dtype)
    sizes = jnp.asarray(DRAWS[draw], jnp.int32)
    live = int(sizes.sum())
    want = np.asarray(jax.lax.ragged_dot(x, w, sizes,
                                         preferred_element_type=F32))
    empty = np.asarray(sizes) == 0
    w = jnp.where(empty[:, None, None], jnp.nan, w)
    x = jnp.where(jnp.arange(R)[:, None] < live, x, jnp.nan)
    for col_tile in (0, 128):               # one tile of N, and two
        got = np.asarray(gm.grouped_matmul(x, w, sizes, row_tile=TM,
                                           col_tile=col_tile,
                                           interpret=True))
        assert got.shape == (R, N) and got.dtype == np.float32
        assert np.isfinite(got[:live]).all()
        np.testing.assert_allclose(got[:live], want[:live],
                                   atol=_tol(dtype, want[:live]))


@pytest.mark.parametrize("dtype", [F32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("draw", ["off_the_tile", "over_a_tile",
                                  "full_buffer"])
def test_gate_and_up_in_one_pass_give_the_hidden_rows(draw, dtype):
    """The fused body against ``silu(g) * u`` of the two ``ragged_dot``
    products: float32 through the activation, cast once to the storage
    dtype."""
    x, w_gate, w_up = _operands(dtype, seed=1)
    sizes = jnp.asarray(DRAWS[draw], jnp.int32)
    live = int(sizes.sum())
    g, u = (jax.lax.ragged_dot(x, w, sizes, preferred_element_type=F32)
            for w in (w_gate, w_up))
    want = np.asarray((jax.nn.silu(g) * u).astype(dtype), np.float32)[:live]
    got = gm.grouped_swiglu(x, w_gate, w_up, sizes, row_tile=TM,
                            interpret=True)
    assert got.shape == (R, N) and got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32)[:live], want,
                               atol=_tol(dtype, want))


@pytest.mark.parametrize("draw,tile,group,n_live", [
    # tile 0 for groups 0 and 2, tile 1 for 2, tile 2 for 2, 3 and 4,
    # tile 3 for 4; past the seven live visits the last one again: its
    # row tile is resident, so the pipeline copies nothing
    ("off_the_tile", [0, 0, 1, 2, 2, 2, 3, 3], [0, 2, 2, 2, 3, 4, 4, 4], 7),
    ("over_a_tile", [0, 1, 2, 3, 3, 3, 3, 3], [0, 0, 0, 3, 3, 3, 3, 3], 4),
    ("one_row_each", [0] * 8, [0, 1, 2, 3, 4, 4, 4, 4], 5),
    ("full_buffer", [0, 1, 2, 2, 3, 3, 3, 3], [1, 1, 1, 4, 4, 4, 4, 4], 5),
    ("every_row_dead", [0] * 8, [4] * 8, 0)])
def test_the_table_of_visits(draw, tile, group, n_live):
    """Row tile and expert of every visit, and each group's visits:
    the first is where its weights are waited for, and the visit at
    ``v_end`` — the next group's first — names the group whose weights
    that wait is followed by starting."""
    sizes = DRAWS[draw]
    got_tile, got_group, starts, ends, v_start, v_end, got_live = (
        np.asarray(a).tolist() for a in gm.visit_tables(
            jnp.asarray(sizes, jnp.int32), rows=R, tm=TM))
    assert (got_tile[:max(n_live, 1)], got_live) == (
        tile[:max(n_live, 1)], [n_live])
    assert got_group[:n_live] == group[:n_live]
    # the dead visits go on naming the last live one's row tile
    assert set(got_tile[n_live:]) <= {tile[max(n_live - 1, 0)]}
    assert ends == np.cumsum(sizes).tolist()
    assert starts == (np.cumsum(sizes) - sizes).tolist()
    with_rows = [g for g, n in enumerate(sizes) if n]
    heads = [v for v in range(n_live) if v == 0 or group[v] != group[v - 1]]
    assert [v_start[g] for g in with_rows] == heads
    assert [v_end[g] for g in with_rows] == (heads[1:] + [n_live])[:len(heads)]
    assert all(v_end[g] == v_start[g] for g, n in enumerate(sizes) if not n)


def test_sizes_past_the_buffer_are_clipped():
    """Groups that would run past the buffer's last row stop there (the
    caller's ``cut`` never does; a bare call is held to the buffer)."""
    tile, group, starts, ends, _v_start, v_end, n_live = (
        np.asarray(a).tolist() for a in gm.visit_tables(
            jnp.asarray([300, 300, 300], jnp.int32), rows=512, tm=128))
    assert (starts, ends, n_live) == ([0, 300, 512], [300, 512, 512], [5])
    assert max(tile) == 3 and group[:5] == [0, 0, 0, 1, 1]
    assert v_end == [3, 5, 5]


@pytest.mark.parametrize("case,rows,k,n,itemsize,want", [
    ("lfm2_up_4096", 16384, 2048, 1792, 2, (128, 1792)),
    ("lfm2_down_2048", 8192, 1792, 2048, 2, (128, 2048)),
    ("granite_up_2048", 6400, 4096, 768, 2, (128, 768)),
    ("granite_down_1024", 3328, 768, 4096, 2, (128, 4096)),
    ("joyai_up", 5120, 2048, 768, 2, (128, 768)),
    ("glm5_up", 5120, 6144, 2048, 2, (128, 512)),      # 6.3 MB a tile
    ("glm5_down", 5120, 2048, 6144, 2, (128, 2048)),
    ("float32_halves_the_columns", 16384, 2048, 1792, 4, (128, 896)),
    ("no_whole_row_tile", 1000, 256, 256, 2, (0, 0)),
    ("no_whole_lane_tiles", 1024, 256, 200, 2, (0, 0)),
    ("contraction_off_the_lanes", 1024, 200, 256, 2, (0, 0))])
def test_tiles_follow_the_shapes(case, rows, k, n, itemsize, want):
    tm, tn = gm.tiles(rows, k, n, itemsize)
    assert (tm, tn) == want
    if tm:
        assert rows % tm == 0 and n % tn == 0 and tn % 128 == 0
        assert k * tn * itemsize <= gm.TILE_BYTES


# (buffer rows, d_model, d_expert) of every cell whose prefills or
# trained sequence take the grouped way
@pytest.mark.parametrize("case,rows,m,f,devices,want", [
    ("lfm2_prefill_4096", 16384, 2048, 1792, 1, "kernel"),
    ("lfm2_prefill_2048", 8192, 2048, 1792, 1, "kernel"),
    ("granite_prefill_2048", 6400, 4096, 768, 1, "kernel"),
    ("granite_prefill_1024", 3328, 4096, 768, 1, "kernel"),
    ("joyai_sequence", 5120, 2048, 768, 1, "kernel"),
    ("glm5_priming", 5120, 6144, 2048, 1, "kernel"),
    ("trinity_priming", 32768, 2048, 1024, 1, "kernel"),
    ("under_a_mesh", 16384, 2048, 1792, 4, "ragged_dot"),
    ("no_whole_lane_tiles", 16384, 2048, 1800, 1, "ragged_dot"),
    ("no_whole_row_tile", 6000, 2048, 1792, 1, "ragged_dot")])
def test_the_rule_reads_shapes_and_the_mesh(case, rows, m, f, devices, want,
                                            monkeypatch):
    """On a TPU (steered: the rule asks ``on_tpu``) the kernel runs the
    products wherever the shapes give whole tiles, off a mesh — from
    (rows, d_model, d_expert, itemsize) and the mesh alone; off the chip
    ``ragged_dot`` stays, the parent's lowered text."""
    from jax.sharding import Mesh
    mesh = Mesh(np.asarray(jax.devices()[:devices]), ("dp",))
    assert expert_ffn.grouped_path(rows, m, f, 2, mesh) == "ragged_dot"
    monkeypatch.setattr(pk, "on_tpu", lambda: True)
    assert expert_ffn.grouped_path(rows, m, f, 2, mesh) == want


def test_the_counter_is_in_the_exporters_catalog():
    from paddle_tpu.observability import exporters, metrics as obs_metrics
    exporters._preregister_catalog()
    assert "paddle_expert_grouped_lowered_total" in \
        obs_metrics.default_registry().snapshot()


# tokens (over DENSE_MAX_TOKENS: the grouped way), d_model, d_expert,
# picks a token
TOKENS, M, F, PICKS = 768, 128, 128, 2


def _layer(case, dtype=F32, seed=0):
    """``two_turns``: 2 of 8 experts held, a buffer of 512 rows, and a
    router that sends them 700 assignments — the loop's second turn;
    ``padded``: all 4 experts held (1 536 rows), the last 200 tokens a
    padded bucket's."""
    rng = np.random.RandomState(seed)
    held, router = {"two_turns": (2, 8), "padded": (4, 4)}[case]
    x = jnp.asarray(rng.randn(TOKENS, M), dtype)
    w_gate, w_up = (jnp.asarray(rng.randn(held, M, F) * 0.1, dtype)
                    for _ in range(2))
    w_down = jnp.asarray(rng.randn(held, F, M) * 0.1, dtype)
    if case == "two_turns":
        idx = np.stack([np.where(np.arange(TOKENS) < 500, 0, 3),
                        np.where(np.arange(TOKENS) % 4 == 0, 1, 5)], axis=1)
        valid = None
    else:
        idx = np.stack([rng.permutation(router)[:PICKS]
                        for _ in range(TOKENS)])
        valid = jnp.arange(TOKENS) < TOKENS - 200
    combine = jnp.asarray(rng.rand(TOKENS, PICKS), F32)
    return (x, combine, jnp.asarray(idx, jnp.int32), w_gate, w_up,
            w_down), valid, router


def _count():
    return {p: expert_ffn.EXPERT_GROUPED_LOWERED.labels(path=p).value
            for p in ("kernel", "ragged_dot")}


@pytest.mark.parametrize("dtype", [F32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", ["two_turns", "padded"])
def test_the_layer_through_the_kernel_gives_the_ragged_dot_ways_sum(
        case, dtype, monkeypatch):
    """``held_experts_part`` whole, the interpreter forced, against the
    ``ragged_dot`` path: the same ``y`` and ``sizes``, one lowering
    counted under each path's label; a padded token gets nothing."""
    args, valid, router = _layer(case, dtype)

    def part(forced):
        monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS", forced)
        before = _count()
        y, sizes = jax.jit(
            lambda *a: expert_ffn.held_experts_part(*a, 0, valid, router))(
            *args)
        took = {p: v - before[p] for p, v in _count().items()}
        return np.asarray(y), np.asarray(sizes), took
    want, want_sizes, took = part("0")
    assert took == {"kernel": 0, "ragged_dot": 1}
    got, sizes, took = part("1")
    assert took == {"kernel": 1, "ragged_dot": 0}
    np.testing.assert_array_equal(sizes, want_sizes)
    if case == "two_turns":
        # more held assignments than the buffer's rows
        assert sizes.sum() == 692 > expert_ffn.grouped_rows(
            TOKENS, PICKS, 2, router) == 512
    assert np.isfinite(got).all() and np.abs(want).max() > 1.0
    np.testing.assert_allclose(got, want, atol=_tol(dtype, want))
    if valid is not None:
        assert not got[TOKENS - 200:].any()


def test_the_backward_recomputes_through_the_kernel(monkeypatch):
    """The grouped way's own backward makes a turn's products again:
    through the kernel where the forward took it, with the gradients of
    the ``ragged_dot`` path."""
    args, valid, router = _layer("two_turns")

    def grads(forced):
        monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS", forced)

        def loss(x, combine, w_gate, w_up, w_down):
            y, _ = expert_ffn.held_experts_part(
                x, combine, args[2], w_gate, w_up, w_down, 0, valid, router)
            return jnp.sum(y * jnp.cos(jnp.arange(M, dtype=F32)))
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(
            args[0], args[1], *args[3:])
    for got, want in zip(grads("1"), grads("0")):
        want = np.asarray(want)
        assert np.abs(want).max() > 0
        np.testing.assert_allclose(np.asarray(got), want,
                                   atol=1e-4 * np.abs(want).max())
