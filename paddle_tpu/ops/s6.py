"""Mamba-1's selective state-space mixer (S6, arXiv:2312.00752) as the
slot server serves it: a FIFTH kind of fixed-size per-slot state
(docs/serving.md "Recurrent state"). ``ops/ssd.py`` is Mamba-2's; the
numbers look alike and the equations differ — the decay here is one
value per CHANNEL AND STATE INDEX, so the state update has no head
structure and the prefill has no matrix form; the step ``dt`` comes
through a low-rank pair with norms of its own; the conv runs over ``x``
alone; nothing norms the inner width.

With u the layer's normed input, ``C = d_inner`` channels, a state of N
and a step rank of R:

    [x | z] = W_in u                         (C | C; x first, the gate second)
    x_t = SiLU(sum_j conv_w[j] * x_{t-K+1+j} + conv_b)       (causal, K taps)
    [dt | B | C] = W_x x_t                   (R | N | N)
    dt = RMSNorm_R(dt) g_dt    B = RMSNorm_N(B) g_B    C = RMSNorm_N(C) g_C
    dt_t[c] = softplus((W_dt dt)[c] + b_dt[c])
    A = -exp(A_log)                          (A_log [N, C])
    h_t[n, c] = exp(dt_t[c] A[n, c]) h_{t-1}[n, c] + dt_t[c] B_t[n] x_t[c]
    y_t[c] = sum_n h_t[n, c] C_t[n] + D[c] x_t[c]
    out = W_out (y_t * SiLU(z_t))

State: ``h`` [n_slots, N, C] float32 — the state index on the sublanes
and the channels on the lanes, ``ops/ssd.py``'s layout — and the conv
window's last K-1 pre-conv rows of ``x`` [n_slots, K-1, C] in the
activation dtype, both persistable and donated. ``A_log`` is kept FLAT,
[N * C] (row ``n`` of [N, C] first): a parameter of rank two is a matrix
to whoever draws a model's weights from a seed, and this one starts at a
fixed value, as ``dt_bias`` and ``D`` do.

- ``s6_prefill`` runs the recurrence over ONE request's prompt from a
  zero state, ``chunk`` rows at a time over the chunks that hold a TRUE
  token (``ceil(seq_len / chunk)``: a padded bucket's empty chunks cost
  nothing); rows at and past ``seq_len`` inside the last one have ``dt =
  0`` and ``x = 0`` and change neither the state nor the conv window.
  The result lands in slot ``Slot`` of both variables (a slot >=
  n_slots drops: the warm-up's dispatch writes nothing). WHAT walks the
  rows is chosen from the shapes (:func:`scan_path`, counted by
  ``paddle_s6_scan_lowered_total{path}``):

  - ``kernel`` — on a TPU, off a mesh, channels in whole lane tiles and
    a chunk of whole sublane tiles: ``ops/pallas/s6_scan.py``, the state
    tile in VMEM, every input read once and ``y`` written once;
  - ``loop`` — everywhere else: a ``lax.scan`` over a chunk's rows
    inside a loop over the live chunks, the state ``[N, C]`` the carry.

  Neither writes an array of ``[T, N, C]``.
- ``s6_decode`` advances every slot by one token; slots with ``Active``
  == 0 keep state and window bit for bit. WHAT updates the state is
  chosen from the shapes too (:func:`state_path`, counted by
  ``paddle_s6_state_lowered_total{path}``): ``kernel`` — on a TPU, off a
  mesh, channels in whole lane tiles and slots in whole blocks of eight:
  ``ops/pallas/s6_state.py``, the state streamed through VMEM once, in
  place, in a call whose time is its own; ``fused`` — everywhere else:
  :func:`state_step`, plain XLA, one pass that reads the state once and
  writes it once (on the v5e it is staged in VMEM whole and its bytes
  move under other instructions' waits: the kernel's docstring has the
  numbers).

Precision: the projections multiply in the storage dtype with float32
accumulation; conv, norms, softplus, ``exp``, the recurrence and ``y``
are float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from paddle_tpu.core.registry import first, register_op
from paddle_tpu.observability import device_scopes as _device_scopes
from paddle_tpu.observability import metrics as _metrics
from paddle_tpu.ops.math_ops import dense

F32 = jnp.float32
_decode_phase = functools.partial(_device_scopes.phase, "s6_decode")
_prefill_phase = functools.partial(_device_scopes.phase, "s6_prefill")

_WEIGHTS = ("WIn", "WOut", "ConvW", "ConvB", "WX", "DtNorm", "BNorm",
            "CNorm", "WDt", "DtBias", "ALog", "D")

# exporter-catalog family (docs/serving.md "Metric names"). Counts
# LOWERINGS of the prefill's scan, labelled with what :func:`scan_path`
# chose to walk its rows.
S6_SCAN_LOWERED = _metrics.counter(
    "paddle_s6_scan_lowered_total",
    "s6_prefill ops lowered, by what walks the prompt's rows (kernel: "
    "ops/pallas/s6_scan.py, the state tile in VMEM|loop: lax.scan over a "
    "chunk's rows, the state the carry)",
    labelnames=("path",))


S6_STATE_LOWERED = _metrics.counter(
    "paddle_s6_state_lowered_total",
    "s6_decode ops lowered, by what updates the slots' state (kernel: "
    "ops/pallas/s6_state.py, streamed through VMEM in place|fused: one "
    "XLA fusion over the whole state)",
    labelnames=("path",))


def _kernel_may_run(mesh) -> bool:
    """A TPU and no mesh of more than one device (``kernel_enabled``), or
    the tests' interpreter."""
    from paddle_tpu.ops import pallas as _plk
    return _plk.kernel_enabled(mesh=mesh) or _plk.forced_interpret()


def state_path(slots: int, channels: int, mesh=None) -> str:
    """What updates a decode step's state, from the shapes the op sees:
    ``"kernel"`` where the kernel may run and the shapes give whole
    blocks; ``"fused"`` otherwise."""
    from paddle_tpu.ops.pallas import s6_state as _k
    return "kernel" if _k.tiles(slots, channels) and _kernel_may_run(mesh) \
        else "fused"


def scan_path(rows: int, channels: int, chunk: int, mesh=None) -> str:
    """What walks a prefill's rows, decided from the shapes the op sees
    and never from a flag: ``"kernel"`` where the kernel may run and the
    shapes give whole tiles; ``"loop"`` otherwise."""
    from paddle_tpu.ops.pallas import s6_scan as _k
    whole = _k.channel_tile(channels) and chunk % 8 == 0 \
        and rows % chunk == 0
    return "kernel" if whole and _kernel_may_run(mesh) else "loop"


def _rms(v, gain, eps):
    return v * jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + eps) \
        * gain.astype(F32)


def _in_proj(u, w):
    """u [T, M] -> the pre-conv rows of x [T, C] in u's dtype (what the
    conv window keeps, so a prefill and the steps after it convolve the
    same numbers) and the gate z [T, C] float32."""
    xz = dense(u, w["WIn"])                      # one product, float32
    inner = xz.shape[1] // 2
    return xz[:, :inner].astype(u.dtype), xz[:, inner:]


def _project(xs, w, eps, dt_):
    """The conv's result xs [T, C] float32 -> the step dt [T, C] (after
    the softplus), B and C [T, N], float32: W_x, Jamba's three norms, the
    step's low-rank way back up."""
    n = w["BNorm"].shape[0]
    r = w["DtNorm"].shape[0]
    low = dense(xs.astype(dt_), w["WX"])                    # [T, R + 2N]
    dt = _rms(low[:, :r], w["DtNorm"], eps)
    b = _rms(low[:, r:r + n], w["BNorm"], eps)
    c = _rms(low[:, r + n:], w["CNorm"], eps)
    dt = jax.nn.softplus(dense(dt.astype(dt_), w["WDt"])
                         + w["DtBias"].astype(F32))
    return dt, b, c


def _a(w):
    """A [N, C] = -exp(A_log), from the flat parameter."""
    n = w["BNorm"].shape[0]
    return -jnp.exp(w["ALog"].astype(F32).reshape(n, -1))


def _output(y, z, w, dt_):
    return dense((y * jax.nn.silu(z)).astype(dt_), w["WOut"], dt_)


def state_step(state, a, dt, x, b, c):
    """One step of the recurrence on state [B, N, C] with a [N, C], dt
    and x [B, C], b and c [B, N]: (the new state, y [B, C] without the D
    term). Elementwise with row and column vectors and a reduction over
    the sublanes: one pass over the state."""
    new = jnp.exp(dt[:, None, :] * a[None]) * state \
        + (dt * x)[:, None, :] * b[:, :, None]
    return new, jnp.sum(new * c[:, :, None], axis=1)


def loop_scan(x, dt, b, c, a, n_chunks, chunk: int):
    """The recurrence from a zero state over rows x, dt [T, C], b, c
    [T, N] (float32; rows that are padding have dt = x = 0), a row at a
    time: a ``lax.scan`` over a chunk's rows inside a loop over the first
    ``n_chunks`` chunks (a traced count): (y [T, C] without the D term —
    zero past the chunks computed —, the state after them [N, C])."""
    t = x.shape[0]
    if t % chunk:
        raise ValueError(f"a prompt bucket of {t} rows is not a whole "
                         f"number of chunks of {chunk}")

    def row(h, r):
        h, y = state_step(h[None], a, r[0][None], r[1][None], r[2][None],
                          r[3][None])
        return h[0], y[0]

    def body(i, carry):
        h, y = carry
        cut = lambda v: jax.lax.dynamic_slice_in_dim(      # noqa: E731
            v, i * chunk, chunk)
        h, rows = jax.lax.scan(row, h, (cut(dt), cut(x), cut(b), cut(c)))
        return h, jax.lax.dynamic_update_slice_in_dim(y, rows, i * chunk,
                                                      axis=0)

    h, y = jax.lax.fori_loop(0, n_chunks, body,
                             (jnp.zeros(a.shape, F32), jnp.zeros_like(x)))
    return y, h


def _weights(ins):
    return {n: first(ins, n) for n in _WEIGHTS}


@register_op("s6_prefill", no_grad=True,
             slot_state=("s6", ("StateOut", "ConvOut")),
             ref="TPU-native serving op: a Mamba-1 (S6, arXiv:2312.00752) "
                 "mixer over one request's prompt, the selective scan over "
                 "the chunks its true length fills, writing the slot's "
                 "state and conv window (ops/s6.py)")
def _s6_prefill(ctx, ins, attrs):
    """X [1,T,M], the layer's weights, State [n_slots,N,C] float32, Conv
    [n_slots,K-1,C], SeqLen [1,1] int, Slot [1,1] int (>= n_slots:
    nothing is written) -> Out [1,T,M], StateOut, ConvOut. attrs: chunk,
    epsilon."""
    x = first(ins, "X")
    w = _weights(ins)
    state, conv = first(ins, "State"), first(ins, "Conv")
    eps = float(attrs.get("epsilon", 1e-6))
    if x.shape[0] != 1:
        raise ValueError("s6_prefill takes one request (batch 1)")
    t, dt_ = x.shape[1], x.dtype
    chunk = min(int(attrs["chunk"]), t)
    taps = w["ConvW"].shape[0]
    n = jnp.asarray(first(ins, "SeqLen")).reshape(()).astype(jnp.int32)
    slot = jnp.asarray(first(ins, "Slot")).reshape((1,)).astype(jnp.int32)
    real = jnp.arange(t)[:, None] < n

    u, z = _in_proj(x[0], w)
    with _prefill_phase("conv"):
        # rows at and past the true length are padding: they must reach
        # neither the conv window that is kept nor the recurrence
        u = jnp.where(real, u, 0)
        padded = jnp.concatenate(
            [jnp.zeros((taps - 1, u.shape[1]), u.dtype), u], axis=0)
        cw = w["ConvW"].astype(F32)
        xs = jax.nn.silu(sum(cw[j] * padded[j:j + t].astype(F32)
                             for j in range(taps)) + w["ConvB"].astype(F32))
        xs = jnp.where(real, xs, 0.0)
        window = jax.lax.dynamic_slice(padded, (n, 0),
                                       (taps - 1, padded.shape[1]))
    with _prefill_phase("project"):
        dt, b, c = _project(xs, w, eps, dt_)
        dt = jnp.where(real, dt, 0.0)
    path = scan_path(t, xs.shape[1], chunk, getattr(ctx, "mesh", None))
    S6_SCAN_LOWERED.labels(path=path).inc()
    with _prefill_phase("scan"):
        n_chunks = (n + chunk - 1) // chunk
        if path == "kernel":
            from paddle_tpu.ops import pallas as _plk
            from paddle_tpu.ops.pallas.s6_scan import s6_scan
            y, s = s6_scan(xs, dt, b, c, _a(w), n_chunks, chunk=chunk,
                           interpret=_plk.interpret_mode())
        else:
            y, s = loop_scan(xs, dt, b, c, _a(w), n_chunks, chunk)
        # the kernel leaves the rows of the chunks it skipped unwritten
        y = jnp.where(real, y + w["D"].astype(F32) * xs, 0.0)
    out = _output(y, z, w, dt_)
    return {"Out": [out[None]],
            "StateOut": [state.at[slot].set(s[None], mode="drop")],
            "ConvOut": [conv.at[slot].set(window[None].astype(conv.dtype),
                                          mode="drop")]}


@register_op("s6_decode", no_grad=True,
             slot_state=("s6", ("StateOut", "ConvOut")),
             ref="TPU-native serving op: one Mamba-1 (S6) step for every "
                 "decode slot, the state and the conv window updated in "
                 "place, inactive slots untouched (ops/s6.py)")
def _s6_decode(ctx, ins, attrs):
    """X [B,1,M] (B = n_slots), the layer's weights, State [B,N,C]
    float32, Conv [B,K-1,C], Active [B,1] int -> Out [B,1,M], StateOut,
    ConvOut. attrs: epsilon."""
    x = first(ins, "X")
    w = _weights(ins)
    state, conv = first(ins, "State"), first(ins, "Conv")
    eps = float(attrs.get("epsilon", 1e-6))
    dt_ = x.dtype
    active = jnp.asarray(first(ins, "Active")).reshape(-1) > 0

    u, z = _in_proj(x[:, 0], w)
    with _decode_phase("conv"):
        window = jnp.concatenate([conv, u[:, None].astype(conv.dtype)],
                                 axis=1)
        xs = jax.nn.silu(
            jnp.sum(w["ConvW"].astype(F32)[None] * window.astype(F32),
                    axis=1) + w["ConvB"].astype(F32))
        conv_new = jnp.where(active[:, None, None], window[:, 1:], conv)
    with _decode_phase("project"):
        dt, b, c = _project(xs, w, eps, dt_)
    path = state_path(state.shape[0], state.shape[2],
                      getattr(ctx, "mesh", None))
    S6_STATE_LOWERED.labels(path=path).inc()
    with _decode_phase("state"):
        if path == "kernel":
            from paddle_tpu.ops import pallas as _plk
            from paddle_tpu.ops.pallas.s6_state import s6_state_update
            state_out, y = s6_state_update(
                state, dt, xs, b, c, _a(w), active,
                interpret=_plk.interpret_mode())
        else:
            new, y = state_step(state, _a(w), dt, xs, b, c)
            state_out = jnp.where(active[:, None, None], new, state)
    out = _output(y + w["D"].astype(F32) * xs, z, w, dt_)
    return {"Out": [out[:, None]], "StateOut": [state_out],
            "ConvOut": [conv_new]}
