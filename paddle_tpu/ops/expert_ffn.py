"""The expert feed-forward layer of a sparse model, as ONE member of an
expert-parallel group computes it: the router scores every token over
ALL ``n_experts`` experts and keeps the ``top_k`` best (by a sigmoid of
every logit, or by the logits themselves with a softmax over the picks:
:func:`route`), and this
program computes the part of the result that the experts it HOLDS
(``[held_start, held_start + n_held)``) give, plus the shared expert
that every member computes alike (where the model has one: a layer
declared without ``SGate`` adds none). What the absent experts would add is
left out — their owners add it (docs/serving.md "Expert layer"); on one
chip the layer runs without that exchange, and nothing here stands in
for it.

Dropless, static shapes, two ways to the same sum, chosen from the
number of tokens in the call (a static shape, never a flag):

- **grouped** (more than ``DENSE_MAX_TOKENS`` tokens: Granite's
  prefills of 1 024 and 2 048 tokens, inside its cell's window; GLM-5's
  and Trinity's while their cells prime): the ``n_tokens * top_k``
  assignments are sorted by held expert, held ones first, and every
  buffer holds the HELD rows alone — :func:`grouped_rows` of them, from
  the shapes the op sees (a quarter more than the held experts' even
  share of the assignments: 6 400 of 20 480 rows at 18 of 72 experts;
  all of them where the member holds every expert), never a capacity
  factor: a draw that holds more takes another turn of a loop whose trip
  count the device decides, and nothing is dropped. What moves is what
  is computed: the held rows' inputs are gathered into the buffer, the
  three grouped products run over it (below: WHAT runs them is chosen
  from the shapes, :func:`grouped_path`), and each token then reads its
  picks' result rows where they lie and sums them weighed, in one pass
  (until PR 44 every buffer was ``[n_tokens * top_k, ·]`` and the result
  went back through a permute, a select, a reshape and a sum of that
  size: 46 of a 2 048-token prefill's 124 ms, for rows three quarters
  of which nobody computed). A pick held elsewhere, a padded token's or
  another turn's is SELECTED out, not weighed by zero: the grouped
  product leaves the rows past its groups as it found them, NaN on the
  chip now and then.

  The three products (:func:`grouped_path`, counted by
  ``paddle_expert_grouped_lowered_total{path}``):

  - ``kernel`` — on a TPU, off a mesh of more than one device, at
    widths of whole lane tiles and a buffer of whole row tiles: two
    calls of ONE row-tiled Mosaic kernel
    (``ops/pallas/grouped_matmul.py``, PR 64). A table built from the
    turn's ``cut`` maps each grid step to (row tile, expert); the tiles
    past the last group do no product and fetch no weight, so the time
    follows the rows HELD, not the buffer's. The first call multiplies
    a row tile by the expert's gate AND up tiles and writes
    ``silu(g) * u`` — float32 through the activation, cast once — as
    the hidden rows (the two ``f32[R, F]`` products are never written);
    the second is the down product, float32 out. The backward's
    recompute of a turn takes the second body for all three;
  - ``ragged_dot`` — everywhere else (the CPU, a program lowered under
    a mesh of several devices, odd widths): ``jax.lax.ragged_dot`` per
    projection, the text every earlier PR lowered. On the v5e it runs
    at 19-70 TFLOP/s and its time follows the BUFFER's rows (PERF.md
    section 6, PRs 44 and 64).

  | prefill / sequence | buffer rows x M x F | held | row tile x gate/up columns, down columns |
  |---|---|---|---|
  | LFM2, 4 096 and 2 048 tokens x 4 of 32 | 16 384 and 8 192 x 2 048 x 1 792 | 32 | 128 x 1 792, 2 048 |
  | Granite, 2 048 and 1 024 x 10 of 72 | 6 400 and 3 328 x 4 096 x 768 | 18 | 128 x 768, 4 096 |
  | JoyAI (trained), 8 192 x 8 of 256 | 5 120 x 2 048 x 768 | 16 | 128 x 768, 2 048 |
  | GLM-5 (priming), 8 192 x 8 of 256 | 5 120 x 6 144 x 2 048 | 16 | 128 x 512, 2 048 |
  | Trinity (priming), 4 096 x 8 of 128 | 32 768 x 2 048 x 1 024 | 128 | 128 x 1 024, 2 048 |
  | MiMo (priming), 16 of 256 held | rows x 4 096 x 2 048 | 16 | 128 x 1 024, 2 048 |

  (``grouped_matmul.tiles``: the widest whole lane tiles that divide
  the columns and keep one weight tile at or under 8 MB.)
- **dense** (a decode step, a prefill of up to 512 tokens): every held
  expert
  multiplies EVERY token, and the combine weight — zero where the token
  did not pick the expert — is applied before the down projection, so
  the sum over experts is part of that one product. Below the chip's
  ridge point (197 TFLOP/s over 819 GB/s: 240 multiply-accumulate rows
  a bf16 weight) multiplying all tokens costs no more time than reading
  an expert's weights does, so the weights' bytes are the cost, and
  WHOSE weights a call streams is chosen from the shapes again
  (:func:`dense_tier`, counted by
  ``paddle_expert_dense_lowered_total{path}``):

  - ``all``, every held expert's, in two fusions — where nearly every
    held expert is hit in a step anyway (128 tokens x 8 picks over 320
    experts: 96 %): the step's cost does not depend on the routing
    draw, and the fusions run at 85-91 % of the HBM rate. On the chip
    the grouped products of a 128-token step read 38 % of the HBM
    roofline and moved with the seed. Above the ridge point this goes
    on winning for a while, because the grouped product's row tiles are
    mostly padding at a few tokens an expert: one layer's held experts
    took 1.76 / 1.89 / 3.47 ms dense against 4.41 / 4.71 / 4.93 ms
    grouped at 128 / 256 / 512 tokens, the lines crossing near 700
    (PERF.md, PR 31);
  - ``skip``, the HIT experts' alone, in one kernel through the down
    product (``ops/pallas/expert_stream.py``, PR 54) — where a uniform
    router would leave at least a tenth of the held experts without a
    token (``SKIP_MIN_UNPICKED``: ``(1 - top_k / n_experts) **
    n_tokens``) in a call at or under the ridge, on a chip, off a mesh,
    at widths of whole lane tiles. The held experts' ``sizes`` are on
    the device before the products start; an expert with none would add
    exact zeros, so its weights are not read and the sum is the same
    sum. The step's cost now FOLLOWS THE DRAW: one layer at GLM-5's
    shape 1.66 ms with 16 of 16 held experts hit, 0.96 with 9 (the two
    fusions 1.65 whatever the draw; PERF.md, PR 54).

  | cell (decode step) | tokens x picks / experts | expected unpicked | read in the cell | the step streams |
  |---|---|---|---|---|
  | ``serve_glm5_decode_longctx`` | 32 x 8 / 256 | 36 % | 40 % | the hit experts (``skip``) |
  | ``serve_trinity_decode_mixedctx`` | 32 x 8 / 128 | 12.7 % | 13 % | the hit experts (``skip``) |
  | ``serve_solar_decode_closed`` | 128 x 8 / 320 | 3.9 % | 7 % | every held expert (``all``) |
  | ``serve_granite_sessions_closed`` | 128 x 10 / 72 | ~0 | 5 % | every held expert (``all``) |
  | ``serve_lfm2_extract_closed`` | 64 x 4 / 32 | 0.02 % | 0.02 % | every held expert (``all``) |

  Solar's prefills of up to 512 tokens, every call under a mesh of more
  than one device and every call off the chip stream every held
  expert's, as before.

``parallel/moe.py`` is the trainer's top-1 layer with capacity drops (op
``moe_ffn``) and is not this.

An expert is ``W_down (SiLU(W_gate x) * W_up x)``. Router scores and
the combine weights are float32; products multiply in the storage dtype
with float32 accumulation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from paddle_tpu.core.registry import first, register_op
from paddle_tpu.observability import device_scopes as _device_scopes
from paddle_tpu.observability import metrics as _metrics
from paddle_tpu.ops.math_ops import amp_dtypes, dense

F32 = jnp.float32
# the expert layer's mechanisms under their declared phases: the
# router and the picks' bookkeeping, the held experts' gate and up
# products, their down product, the shared expert
_phase = functools.partial(_device_scopes.phase, "expert_ffn_held")


def route(x, router_w, top_k: int, norm_topk: bool, scaling: float,
          bias=None, scoring: str = "sigmoid"):
    """x [N, M] -> (combine weights [N, top_k] float32, expert ids
    [N, top_k]): sigmoid scores over every expert, the best ``top_k``,
    renormalised over the picks when ``norm_topk``. With a correction
    ``bias`` [1, E] (DeepSeek-V3's ``noaux_tc``) the picks are the best
    by ``score + bias`` and the weights the picks' scores, without it.
    ``scoring="softmax_topk"`` (Granite's ``GraniteMoeTopKGating``): the
    best ``top_k`` by LOGIT, weighed by a softmax over those logits
    alone (no bias, nothing to renormalise)."""
    if scoring == "softmax_topk":
        vals, idx = jax.lax.top_k(dense(x, router_w), top_k)
        return jax.nn.softmax(vals, axis=-1) * scaling, idx
    if scoring != "sigmoid":
        raise ValueError(f"a router scores by 'sigmoid' or 'softmax_topk', "
                         f"not {scoring!r}")
    scores = jax.nn.sigmoid(dense(x, router_w))
    if bias is None:
        vals, idx = jax.lax.top_k(scores, top_k)
    else:
        _, idx = jax.lax.top_k(scores + bias.astype(F32), top_k)
        vals = jnp.take_along_axis(scores, idx, axis=-1)
    if norm_topk:
        vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
    return vals * scaling, idx


# a call whose hidden activations [N, F] hold more values than this runs
# in blocks of ``SWIGLU_ROW_BLOCK`` rows: a 32768-token prefill of a dense
# layer 16384 wide keeps 2.1 GB of float32 beside 1.1 GB of bfloat16
# otherwise, and does not fit beside the weights and the pages (PR 56).
# Every call of the configurations served before is under it (the
# largest: 16384 rows x 6144) and lowers what it did
SWIGLU_WHOLE_MAX = 1 << 28
SWIGLU_ROW_BLOCK = 4096


def swiglu(x, w_gate, w_up, w_down):
    """W_down (SiLU(W_gate x) * W_up x) for x [N, M] -> [N, M] float32."""
    def whole(rows):
        hidden = jax.nn.silu(dense(rows, w_gate)) * dense(rows, w_up)
        return dense(hidden.astype(x.dtype), w_down)

    n, blk = x.shape[0], SWIGLU_ROW_BLOCK
    if n * w_gate.shape[1] > SWIGLU_WHOLE_MAX and n % blk == 0:
        return jax.lax.map(whole, x.reshape(n // blk, blk, -1)
                           ).reshape(n, -1)
    return whole(x)


# at or under this many tokens a call takes the dense way (module
# docstring: the largest prompt bucket under the measured crossover of
# ~700 tokens, so every size a cell serves takes one way)
DENSE_MAX_TOKENS = 512
# The dense way streams the HIT experts' weights alone
# (ops/pallas/expert_stream.py) where a uniform router would leave at
# least this share of the held experts unpicked in a call: an expert of
# ``n_experts`` misses one token's ``top_k`` picks with probability
# 1 - top_k / n_experts, all ``n_tokens`` of them with that to the power
# n_tokens (:func:`unpicked_share`; a trained router is more skewed, not
# less). GLM-5's step (32 x 8 / 256) expects 36 % and reads 40 % in its
# cell, Trinity's (32 x 8 / 128) 12.7 % and 13 %; Solar's (128 x 8 /
# 320: 3.9 %), Granite's (128 x 10 / 72) and LFM2's (64 x 4 / 32) are
# under a tenth: nearly every expert is hit, the fusions already run at
# 85-91 % of the HBM rate and a step whose cost does not move with the
# draw is worth keeping. Measured before the constant was set (the op
# alone on one v5e, PERF.md section 6, PR 54): at EVERY held expert hit
# the kernel takes the fusions' time or less (GLM-5's layer 1.653
# against 1.658 ms, Trinity's 2.193 against 2.319), so nothing is lost
# at the worst draw and an unpicked share is that share of the bytes
# saved (0.958 ms at 9 of 16 hit, 1.939 at 113 of 128): Trinity, nearest
# the line, gains a tenth of its step and falls on the engaging side.
SKIP_MIN_UNPICKED = 0.1
# ... and the call is a decode-sized one: at or under the chip's ridge
# (module docstring: 240 rows a bf16 weight), where the bytes and not
# the products set the time
SKIP_MAX_TOKENS = 240

# exporter-catalog family (docs/serving.md "Metric names"). Counts
# LOWERINGS, not steps: one increment each time an expert layer is
# traced the dense way, labelled with what :func:`dense_tier` chose. In
# the window the engaged share is 100 - moe_experts_hit_pct (row 1 of
# ``Counts``).
EXPERT_DENSE_LOWERED = _metrics.counter(
    "paddle_expert_dense_lowered_total",
    "Expert layers lowered the dense way, by whose weights a call "
    "streams (skip: the hit experts'|all: every held expert's)",
    labelnames=("path",))


def unpicked_share(n_tokens: int, top_k: int, n_experts: int) -> float:
    """The share of experts a uniform router leaves without a token in
    a call of ``n_tokens`` tokens of ``top_k`` picks over ``n_experts``."""
    return (1.0 - top_k / n_experts) ** n_tokens


def dense_tier(n_tokens: int, top_k: int, n_experts: int, d_model: int,
               d_expert: int, mesh=None) -> str:
    """Whose weights the dense way streams, decided from the shapes the
    op sees and never from a flag: ``"skip"`` (the hit experts' alone,
    through the kernel) for a decode-sized call whose expected unpicked
    share is at least ``SKIP_MIN_UNPICKED``, where the kernel may run (a
    TPU, no mesh of more than one device, widths of whole lane tiles —
    ``kernel_enabled`` — or the tests' interpreter); ``"all"`` (every
    held expert's, the two fusions) otherwise."""
    from paddle_tpu.ops import pallas as _plk
    engages = (
        n_tokens <= SKIP_MAX_TOKENS
        and unpicked_share(n_tokens, top_k, n_experts) >= SKIP_MIN_UNPICKED
        and d_model % 128 == d_expert % 128 == 0
        and (_plk.kernel_enabled(mesh=mesh) or _plk.forced_interpret()))
    return "skip" if engages else "all"


# exporter-catalog family (docs/serving.md "Metric names"). Counts
# LOWERINGS of the grouped way, labelled with what :func:`grouped_path`
# chose for its three products.
EXPERT_GROUPED_LOWERED = _metrics.counter(
    "paddle_expert_grouped_lowered_total",
    "Expert layers lowered the grouped way, by what runs their three "
    "grouped products (kernel: ops/pallas/grouped_matmul.py|ragged_dot: "
    "jax.lax.ragged_dot)",
    labelnames=("path",))


def grouped_path(rows: int, d_model: int, d_expert: int, itemsize: int,
                 mesh=None) -> str:
    """What runs the grouped way's three products over a buffer of
    ``rows`` rows, decided from the shapes the op sees and never from a
    flag: ``"kernel"`` (``ops/pallas/grouped_matmul.py``: row tiles that
    follow the groups, gate and up in one pass with the activation)
    where the kernel may run (a TPU, no mesh of more than one device —
    ``kernel_enabled`` — or the tests' interpreter) and the shapes give
    whole tiles (``grouped_matmul.tiles``: widths of whole lane tiles,
    rows in whole tiles of 128); ``"ragged_dot"``
    (``jax.lax.ragged_dot``, three calls) otherwise."""
    from paddle_tpu.ops import pallas as _plk
    from paddle_tpu.ops.pallas import grouped_matmul as _gm
    engages = (
        all(_gm.tiles(rows, k, n, itemsize)[0]
            for k, n in ((d_model, d_expert), (d_expert, d_model)))
        and (_plk.kernel_enabled(mesh=mesh) or _plk.forced_interpret()))
    return "kernel" if engages else "ragged_dot"


def grouped_rows(n: int, k: int, n_held: int, n_experts: int) -> int:
    """Rows of the grouped way's buffers for ``n`` tokens of ``k`` picks
    over a router ``n_experts`` wide of which ``n_held`` are held here:
    a quarter more than the held experts' even share of the ``n * k``
    assignments, in whole tiles of 256, at most all of them (a member
    that holds every expert). A draw that holds more takes another turn
    of :func:`held_experts_part`'s loop; nothing is dropped."""
    if n_held >= n_experts:
        return n * k
    share = -(-n * k * n_held * 5 // (n_experts * 4))
    return min(n * k, -(-share // 256) * 256)


def held_experts_part(x, combine, idx, w_gate, w_up, w_down,
                      held_start: int, valid=None, n_experts=None,
                      mesh=None):
    """The routed part of the layer that the held experts give: x [N, M],
    combine / idx [N, K], w_gate / w_up [E_held, M, F], w_down
    [E_held, F, M] -> (y [N, M] float32, tokens per held expert
    [E_held] int32). Tokens with ``valid`` false are routed nowhere.
    ``n_experts`` is the router's width (the grouped way sizes its
    buffers by the held share, :func:`grouped_rows`; every assignment's
    worth when None — the held experts are all there are), ``mesh`` the
    one the caller lowers under (:func:`dense_tier`)."""
    n, k = idx.shape
    n_held = w_gate.shape[0]
    if n_experts is None:
        n_experts = n_held
    if n <= DENSE_MAX_TOKENS:
        # the products multiply in x's dtype (a no-op but for float32
        # master weights under the mixed-precision rewrite; the grouped
        # way casts its own, so that their gradient comes back float32)
        w_gate, w_up, w_down = (w.astype(x.dtype)
                                for w in (w_gate, w_up, w_down))
    with _phase("route"):
        local = idx - held_start
        held = (local >= 0) & (local < n_held)
        if valid is not None:
            held &= valid[:, None]
        key = jnp.where(held, local, n_held)                     # [N, K]
        picked = key[:, :, None] == jnp.arange(n_held)           # [N, K, E]
        sizes = jnp.sum(picked, axis=(0, 1), dtype=jnp.int32)
    if n <= DENSE_MAX_TOKENS:
        with _phase("route"):
            # [N, E]: the token's combine weight for the expert, or zero
            w = jnp.sum(jnp.where(picked, combine[:, :, None], 0.0), axis=1)
        tier = dense_tier(n, k, n_experts, x.shape[1], w_gate.shape[2], mesh)
        EXPERT_DENSE_LOWERED.labels(path=tier).inc()
        if tier == "skip":
            # one kernel through the down product (the hidden rows stay
            # in VMEM); an unhit expert would add exact zeros
            from paddle_tpu.ops import pallas as _plk
            from paddle_tpu.ops.pallas.expert_stream import hit_experts
            with _phase("up"):
                y = hit_experts(x, w, sizes, w_gate, w_up, w_down,
                                interpret=_plk.interpret_mode())
            return y, sizes
        with _phase("up"):
            hidden = jax.nn.silu(_all_tokens(x, w_gate)) \
                * _all_tokens(x, w_up) * w[:, :, None]           # [N, E, F]
        with _phase("down"):
            y = jax.lax.dot_general(
                hidden.astype(x.dtype), w_down,
                (((1, 2), (0, 1)), ((), ())), preferred_element_type=F32)
        return y, sizes
    rows = grouped_rows(n, k, n_held, n_experts)
    path = grouped_path(rows, x.shape[1], w_gate.shape[2], x.dtype.itemsize,
                        mesh)
    EXPERT_GROUPED_LOWERED.labels(path=path).inc()
    return _grouped_way(x, combine, w_gate, w_up, w_down, held, key, sizes,
                        rows, path == "kernel"), sizes


# the grouped way's combine is written out pick by pick while a token
# result [N, M] holds at most this many values; a longer prefill's loops
# over its picks (8 gathered float32 results of a 32768-token prefill at
# a width of 4096 are 4 GB at once otherwise; PR 56). Every prefill of the
# configurations served before is under it (the largest: 16384 x 2048)
COMBINE_UNROLLED_MAX = 1 << 26


def _plan(key, sizes, rows):
    """The grouped way's bookkeeping, from each assignment's ``key``
    [N, K] (its held expert, or E_held for one held elsewhere) and the
    held experts' ``sizes``: ``order`` [N*K] the assignment at each
    sorted position (held ones first, by expert), ``at`` [N, K] the
    position of each assignment, ``token`` the token of each sorted
    position (padded to whole turns of ``rows``), and where each held
    expert's rows start and end."""
    n, k = key.shape
    n_held = sizes.shape[0]
    order = jnp.argsort(key.reshape(-1), stable=True)            # [N*K]
    at = jnp.argsort(order).astype(jnp.int32).reshape(n, k)
    turns = -(-n * k // rows)
    token = jnp.pad((order // k).astype(jnp.int32),
                    (0, turns * rows - n * k))
    # where each held expert's rows end (a masked sum, not a
    # cumsum: that lowers to a window reduction, which the long
    # prefills' compile tests keep out of their modules)
    e = jnp.arange(n_held)
    ends = jnp.sum(jnp.where(e[:, None] <= e[None, :],
                             sizes[:, None], 0), axis=0)
    return order, at, token, ends - sizes, ends


def _grouped_way(x, combine, w_gate, w_up, w_down, held, key, sizes, rows,
                 kernel):
    """The grouped way (module docstring): y [N, M] float32 from the
    held assignments alone, ``rows`` sorted positions a turn, the
    products through the ``kernel`` or ``ragged_dot``
    (:func:`grouped_path`). The weights multiply in x's dtype (float32
    master weights under the mixed-precision rewrite are cast here, so
    that their gradient comes back float32)."""
    n, k = key.shape
    w_gate, w_up, w_down = (w.astype(x.dtype) for w in (w_gate, w_up,
                                                        w_down))
    with _phase("route"):
        # held assignments first, by expert (an assignment held elsewhere
        # carries the largest key)
        _, at, token, starts, ends = _plan(key, sizes, rows)
        turns = -(-n * k // rows)

    def turn(i, y):
        """Sorted positions [i * rows, (i + 1) * rows): the held rows
        among them through the three products, and their weighed sum
        added to each token's row of ``y``."""
        lo = i * rows
        with _phase("route"):
            xs = jnp.take(x, jax.lax.dynamic_slice(token, (lo,), (rows,)),
                          axis=0, mode="clip")                   # [R, M]
            # each held expert's rows inside this turn
            cut = jnp.clip(ends, lo, lo + rows) \
                - jnp.clip(starts, lo, lo + rows)
        with _phase("up"):
            hidden = _grouped_hidden(xs, w_gate, w_up, cut, kernel)
        with _phase("down"):
            ys = _product(kernel)(hidden, w_down, cut)           # [R, M]
            # back to token order in one pass: a token reads its picks'
            # rows where they are. Rows past the turn's groups are never
            # computed: on the chip the grouped product leaves them as
            # it found them (NaN now and then, which a weight of 0 does
            # not silence: a padded position's NaN reached the next
            # layer's keys; PERF.md, PR 37), so a pick held elsewhere,
            # a padded token's or another turn's is selected out, not
            # weighed
            here = held & (at >= lo) & (at < lo + rows)
            row = jnp.clip(at - lo, 0, rows - 1)
            if n * x.shape[1] > COMBINE_UNROLLED_MAX:
                # a pick at a time: written out, the compiler keeps all
                # k gathered [N, M] float32 results at once
                def add_pick(j, acc):
                    col = lambda z: jax.lax.dynamic_index_in_dim(  # noqa
                        z, j, axis=1, keepdims=False)
                    return acc + jnp.where(
                        col(here)[:, None],
                        jnp.take(ys, col(row), axis=0, mode="clip")
                        * col(combine)[:, None], 0.0)
                # (a single turn starts from the scalar 0.0)
                return jax.lax.fori_loop(
                    0, k, add_pick, y + jnp.zeros((n, x.shape[1]), F32))
            for j in range(k):
                y = y + jnp.where(
                    here[:, j, None],
                    jnp.take(ys, row[:, j], axis=0, mode="clip")
                    * combine[:, j, None], 0.0)
        return y

    if turns == 1:
        return turn(0, 0.0)
    # as many turns as the draw's held assignments fill: one, unless
    # the router sends this member far more than its even share
    return jax.lax.fori_loop(0, -(-ends[-1] // rows), turn,
                             jnp.zeros((n, x.shape[1]), F32))


def _grouped_way_fwd(x, combine, w_gate, w_up, w_down, held, key, sizes,
                     rows, kernel):
    y = _grouped_way(x, combine, w_gate, w_up, w_down, held, key, sizes,
                     rows, kernel)
    return y, (x, combine, w_gate, w_up, w_down, held, key, sizes)


def _grouped_way_bwd(rows, kernel, res, dy):
    """The grouped way's backward, grouped too: per turn the held rows'
    products are made again from their inputs (nothing of a turn is
    kept), their cotangents go through the transposed grouped products
    (``jax.lax.ragged_dot_general``: over the experts' own rows, never
    every expert times every token), and what the forward gathered is
    gathered back — a sorted row reads ITS token's cotangent, a token
    reads ITS picks' rows; no scatter. Weight gradients accumulate in
    float32 over the turns (as many as the forward took)."""
    x, combine, w_gate, w_up, w_down, held, key, sizes = res
    n, k = key.shape
    cdt = x.dtype
    wg, wu, wd = (w.astype(cdt) for w in (w_gate, w_up, w_down))
    with _phase("route"):
        order, at, token, starts, ends = _plan(key, sizes, rows)
        # each sorted position's combine weight
        weight = jnp.pad(combine.reshape(-1)[order],
                         (0, token.shape[0] - n * k))
    dy = dy.astype(F32)

    def turn(i, carry):
        dx, dcomb, dwg, dwu, dwd = carry
        lo = i * rows
        with _phase("route"):
            tok = jax.lax.dynamic_slice(token, (lo,), (rows,))
            xs = jnp.take(x, tok, axis=0, mode="clip")
            cut = jnp.clip(ends, lo, lo + rows) \
                - jnp.clip(starts, lo, lo + rows)
            # the rows the turn's groups cover: the others were never
            # computed and hold whatever lay there
            live = (jnp.arange(rows) < jnp.clip(ends[-1] - lo, 0, rows)
                    )[:, None]
            dys = jnp.take(dy, tok, axis=0, mode="clip")         # [R, M]
            d_ys = jnp.where(
                live, dys * jax.lax.dynamic_slice(weight, (lo,),
                                                  (rows,))[:, None],
                0.0).astype(cdt)
        with _phase("up"):
            g, u = (_product(kernel)(xs, w, cut) for w in (wg, wu))
            sg = jax.nn.sigmoid(g)
            act = g * sg
            hidden = (act * u).astype(cdt)
        with _phase("down"):
            ys = _product(kernel)(hidden, wd, cut)
            d_weight = jnp.where(live[:, 0], jnp.sum(ys * dys, axis=-1), 0.0)
            dh = _grouped_into(d_ys, wd, cut)                    # [R, F]
            dwd = dwd + _grouped_outer(hidden, d_ys, cut)
        with _phase("up"):
            dg = jnp.where(live, dh * u * (sg * (1.0 + g * (1.0 - sg))),
                           0.0).astype(cdt)
            du = jnp.where(live, dh * act, 0.0).astype(cdt)
            dwg = dwg + _grouped_outer(xs, dg, cut)
            dwu = dwu + _grouped_outer(xs, du, cut)
            d_xs = _grouped_into(dg, wg, cut) + _grouped_into(du, wu, cut)
        with _phase("route"):
            here = held & (at >= lo) & (at < lo + rows)
            row = jnp.clip(at - lo, 0, rows - 1)
            for j in range(k):
                dx = dx + jnp.where(
                    here[:, j, None],
                    jnp.take(d_xs, row[:, j], axis=0, mode="clip"), 0.0)
            dcomb = dcomb + jnp.where(
                here, jnp.take(d_weight, row.reshape(-1), axis=0,
                               mode="clip").reshape(n, k), 0.0)
        return dx, dcomb, dwg, dwu, dwd

    zero = (jnp.zeros(x.shape, F32), jnp.zeros(combine.shape, F32),
            jnp.zeros(w_gate.shape, F32), jnp.zeros(w_up.shape, F32),
            jnp.zeros(w_down.shape, F32))
    if -(-n * k // rows) == 1:
        out = turn(0, zero)
    else:
        out = jax.lax.fori_loop(0, -(-ends[-1] // rows), turn, zero)
    dx, dcomb, dwg, dwu, dwd = out
    return (dx.astype(x.dtype), dcomb.astype(combine.dtype),
            dwg.astype(w_gate.dtype), dwu.astype(w_up.dtype),
            dwd.astype(w_down.dtype), None, None, None)


_grouped_way = jax.custom_vjp(_grouped_way, nondiff_argnums=(8, 9))
_grouped_way.defvjp(_grouped_way_fwd, _grouped_way_bwd)


def _all_tokens(x, w):
    """x [N, M] through every expert's w [E, M, F] -> [N, E, F] float32."""
    return jax.lax.dot_general(x.astype(w.dtype), w,
                               (((1,), (1,)), ((), ())),
                               preferred_element_type=F32)


def _grouped(rows, w, sizes):
    """Each group's rows [R, A] through its expert's w [E, A, B] ->
    [R, B] float32; rows past the groups hold anything."""
    return jax.lax.ragged_dot(rows.astype(w.dtype), w, sizes,
                              preferred_element_type=F32)


def _grouped_kernel(rows, w, sizes):
    """:func:`_grouped` through ``ops/pallas/grouped_matmul.py``."""
    from paddle_tpu.ops import pallas as _plk
    from paddle_tpu.ops.pallas.grouped_matmul import grouped_matmul
    return grouped_matmul(rows.astype(w.dtype), w, sizes,
                          interpret=_plk.interpret_mode())


def _product(kernel):
    """The grouped product :func:`grouped_path` chose."""
    return _grouped_kernel if kernel else _grouped


def _grouped_hidden(rows, w_gate, w_up, sizes, kernel):
    """SiLU(rows W_gate) * (rows W_up) by group, in float32, cast once
    to the rows' dtype: [R, F]. The kernel makes both products in one
    pass over the rows and writes the hidden rows alone."""
    if kernel:
        from paddle_tpu.ops import pallas as _plk
        from paddle_tpu.ops.pallas.grouped_matmul import grouped_swiglu
        return grouped_swiglu(rows, w_gate, w_up, sizes,
                              interpret=_plk.interpret_mode())
    return (jax.nn.silu(_grouped(rows, w_gate, sizes))
            * _grouped(rows, w_up, sizes)).astype(rows.dtype)


def _grouped_into(d_out, w, sizes):
    """The grouped product's transpose to its rows: d_out [R, B] against
    each row's expert's w [E, A, B] -> [R, A] float32."""
    return jax.lax.ragged_dot_general(
        d_out.astype(w.dtype), w, sizes, jax.lax.RaggedDotDimensionNumbers(
            dot_dimension_numbers=(([1], [2]), ([], [])),
            lhs_ragged_dimensions=[0], rhs_group_dimensions=[0]),
        preferred_element_type=F32)


def _grouped_outer(rows, d_out, sizes):
    """The grouped product's transpose to its weights: each expert's
    rows [R, A] against their cotangents [R, B] -> [E, A, B] float32."""
    return jax.lax.ragged_dot_general(
        rows, d_out.astype(rows.dtype), sizes,
        jax.lax.RaggedDotDimensionNumbers(
            dot_dimension_numbers=(([0], [0]), ([], [])),
            lhs_ragged_dimensions=[0], rhs_group_dimensions=[]),
        preferred_element_type=F32)


@register_op("expert_ffn_held",
             ref="one expert-parallel member's share of a top-k routed "
                 "expert layer with a shared expert — dropless, static "
                 "shapes; every held expert over all tokens for a "
                 "step's few, grouped products over the held experts' "
                 "sorted rows for a prefill's or a training step's many "
                 "(a row-tiled Mosaic kernel on one TPU, "
                 "ops/pallas/grouped_matmul.py; jax.lax.ragged_dot "
                 "elsewhere), its backward grouped too "
                 "(ops/expert_ffn.py)")
def _expert_ffn_held(ctx, ins, attrs):
    """X [B,T,M], RouterW [M,E], WGate/WUp [E_held,M,F], WDown
    [E_held,F,M], optional SGate/SUp [M,Fs], SDown [Fs,M] (the shared
    expert; a layer without one declares none and lowers no such branch),
    optional RouterBias [1,E] float32 (the picks are by score + bias:
    :func:`route`), optional Valid [B*T, 1] int or SeqLen [1,1] int (which tokens are
    real: an inactive slot's or a padded position's token is routed
    nowhere), optional Counts [2,E_held] int32 (in place: row 0 the
    tokens each held expert has been given, row 1 the calls in which it
    was given any; they wrap, a reader takes differences) -> Out
    [B,T,M] (+ CountsOut; + Load [E] int32 where the op declares it:
    the real tokens' picks over ALL the router's experts, what
    ``router_bias_update`` balances; + Picks [B*T, top_k] int32 where
    the op declares that output: every token's picked experts — no
    builder declares it, a reader of routing decisions adds it to its
    own copy of a program). attrs: top_k, held_start,
    norm_topk, scaling, scoring (:func:`route`; sigmoid when absent).
    Under the mixed-precision tags the products multiply in bfloat16
    over float32 master weights; the router scores in float32."""
    x = first(ins, "X")
    b, t, m = x.shape
    dt, out_dt = amp_dtypes(x, attrs)
    x2 = x.reshape(b * t, m).astype(dt)
    valid = first(ins, "Valid")
    if valid is not None:
        valid = jnp.asarray(valid).reshape(-1) > 0
    elif first(ins, "SeqLen") is not None:
        valid = jnp.arange(b * t) < jnp.asarray(
            first(ins, "SeqLen")).reshape(()).astype(jnp.int32)
    bias = first(ins, "RouterBias")
    with _phase("route"):
        combine, idx = route(
            x2, first(ins, "RouterW"), int(attrs["top_k"]),
            bool(attrs.get("norm_topk", True)),
            float(attrs.get("scaling", 1.0)),
            *(() if bias is None else (bias,)),
            **({"scoring": attrs["scoring"]} if "scoring" in attrs else {}))
    y, sizes = held_experts_part(
        x2, combine, idx, first(ins, "WGate"), first(ins, "WUp"),
        first(ins, "WDown"), int(attrs.get("held_start", 0)), valid,
        first(ins, "RouterW").shape[1], getattr(ctx, "mesh", None))
    if first(ins, "SGate") is not None:
        with _phase("shared"):
            y = y + swiglu(x2, *(first(ins, n).astype(dt)
                                 for n in ("SGate", "SUp", "SDown")))
    out = {"Out": [y.astype(out_dt).reshape(b, t, m)]}
    if attrs.get("load"):
        with _phase("route"):
            picks = idx[:, :, None] == jnp.arange(
                first(ins, "RouterW").shape[1])
            if valid is not None:
                picks &= valid[:, None, None]
            out["Load"] = [jnp.sum(picks, axis=(0, 1), dtype=jnp.int32)]
    op = getattr(ctx, "op", None)      # a bare call has no description
    if op is not None and "Picks" in op.outputs:
        out["Picks"] = [idx.astype(jnp.int32)]
    counts = first(ins, "Counts")
    if counts is not None:
        seen = jnp.stack([sizes, (sizes > 0).astype(jnp.int32)])
        out["CountsOut"] = [counts + seen.astype(counts.dtype)]
    return out


@register_op("router_bias_update", no_grad=True,
             ref="the auxiliary-loss-free balancing of DeepSeek-V3 "
                 "(arXiv:2412.19437, section 2.1.2; config.json's "
                 "topk_method noaux_tc): after a step a router's "
                 "correction bias moves by gamma toward the experts the "
                 "step gave fewer tokens than the mean "
                 "(ops/expert_ffn.py)")
def _router_bias_update(ctx, ins, attrs):
    """Bias [1,E] float32 (in place), Load [E] int (the step's picks per
    expert, ``expert_ffn_held``'s), optional LoadTotal [E] int32 (in
    place: the picks so far, what a load metric reads; wraps) ->
    BiasOut = Bias + gamma * sign(mean(Load) - Load) (+ LoadTotalOut).
    No gradient reaches the bias: this is its whole update."""
    bias = first(ins, "Bias")
    load = jnp.asarray(first(ins, "Load")).astype(F32)
    step = float(attrs["gamma"]) * jnp.sign(jnp.mean(load) - load)
    out = {"BiasOut": [bias + step.reshape(bias.shape).astype(bias.dtype)]}
    total = first(ins, "LoadTotal")
    if total is not None:
        out["LoadTotalOut"] = [total + load.astype(total.dtype)]
    return out


@register_op("swiglu_ffn",
             ref="a dense SwiGLU feed-forward layer, W_down (SiLU(W_gate "
                 "x) * W_up x): products in the storage dtype with "
                 "float32 accumulation (ops/expert_ffn.py:swiglu)")
def _swiglu_ffn(ctx, ins, attrs):
    """X [B,T,M], WGate / WUp [M,F], WDown [F,M] -> Out [B,T,M]
    (bfloat16 products over float32 master weights under the
    mixed-precision tags)."""
    x = first(ins, "X")
    dt, out_dt = amp_dtypes(x, attrs)
    y = swiglu(x.reshape(-1, x.shape[-1]).astype(dt),
               *(first(ins, n).astype(dt)
                 for n in ("WGate", "WUp", "WDown")))
    return {"Out": [y.astype(out_dt).reshape(x.shape)]}
