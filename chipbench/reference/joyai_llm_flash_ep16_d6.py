"""Plain reference of JoyAI-LLM-Flash's training step as
``chipbench/configs/joyai_llm_flash_ep16_d6.json`` cuts it: jax.numpy,
float32, matmul precision "highest", no kernel; the loss, ``jax.grad``
of it, and the routers' bias update.

The model (config.json gives the sizes; the rest is the configuration's
``assumed``): pre-norm residual layers, RMSNorm eps 1e-6.

- MLA: cQ = RMSNorm(W_DQ h); q = W_UQ cQ -> H x [q_nope dn ; q_rope dr];
  [cKV ; kR] = W_DKV h, cKV <- RMSNorm(cKV); rotary (theta, interleaved
  pairs, no scaling) on q_rope and on kR, which all heads share;
  [k_nope_i ; v_i] = W_UK/UV cKV; s = (q_nope . k_nope + q_rope . kR) /
  sqrt(dn + dr), causal softmax; W_O.
- FFN: the first ``first_k_dense`` layers SwiGLU of ``d_inner``; after
  them s = sigmoid(W_r h), picks = the ``n_experts_per_tok`` largest of
  s + b, weights s[picks] / sum(s[picks]) x routed_scaling_factor, the
  HELD experts' SwiGLU(d_expert) parts summed (what the absent experts
  would add is left out, as in the program), plus the shared SwiGLU.
- MTP (DeepSeek-V3 report, section 2.2): h'_i = W_eh [RMSNorm_e(Emb(
  t_{i+1})) ; RMSNorm_h(h_i)] over the first T - 1 positions, h_i the
  last main layer's output before the final norm; one more layer; the
  final norm, the head and the table shared with the main model; target
  t_{i+2}.
- loss = mean CE + mtp_weight x mean MTP CE.
- bias: b_e <- b_e + gamma sign(mean load - load_e), the load the
  sequence's picks over ALL n_routed_experts experts; no gradient.
- every other parameter: Adam (``adam_step``), float32 moments.

Parameters come as a dict by the program's names less the model's
prefix (``param_shapes``: "emb", "l0_mla.wdq", "mtp0_eh_proj", ...).
Only the matrices named in ``which`` are differentiated; every layer is
recomputed in the backward and the attention runs in query blocks, so
that the reference fits beside a trainer's state at 8 192 tokens.

``low_precision=True`` is NOT the reference: the same step with every
product's operands rounded to float8_e4m3 (what the configuration states
as bfloat16) and the float32 quantities (scores, norms, softmax) to
bfloat16, to show that the limits of the comparison lie between what
the trained path reads and what a path one precision down would read.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_BLOCK = 512

_MLA = ("wdq", "q_norm", "wuq", "wdkv", "kv_norm", "wuk", "wuv", "wo")


class Prec:
    """Where the step rounds. The reference rounds nowhere; ``low`` is
    the precision below the configuration's: float8_e4m3 for a
    product's operands (``m``), bfloat16 for a float32 quantity
    (``f``)."""

    def __init__(self, low=False):
        self.low = bool(low)

    # the rounding is of the VALUE alone: its gradient passes through as
    # it came (a cotangent rounded to float8 would underflow to zero)

    def m(self, x):
        return x + jax.lax.stop_gradient(
            x.astype(jnp.float8_e4m3fn).astype(F32) - x) if self.low else x

    def f(self, x):
        return x + jax.lax.stop_gradient(jax.lax.reduce_precision(
            x, exponent_bits=8, mantissa_bits=7) - x) if self.low else x

    def dot(self, x, w):
        return self.m(x) @ self.m(w)


def layer_tags(build: dict) -> list:
    """[(tag, dense_ffn)] of the main layers, then the MTP layer."""
    out = [(f"l{i}", i < build.get("first_k_dense", 0))
           for i in range(build["n_layer"])]
    return out + [("mtp0", False)] * build.get("mtp_layers", 0)


def param_shapes(build: dict) -> list:
    """[(role, shape)] in the program's creation order; a role is the
    parameter's name less the model's prefix."""
    m, h, v = build["d_model"], build["n_head"], build["vocab"]
    ql, dc = build["q_lora_rank"], build["kv_lora_rank"]
    dn, dr, dv = (build["qk_nope_head_dim"], build["qk_rope_head_dim"],
                  build["v_head_dim"])
    e, held, f = (build["n_routed_experts"], build["n_experts_held"],
                  build["d_expert"])
    fs = build.get("n_shared_experts", 1) * f

    def layer(tag, dense):
        out = [(f"{tag}_ln1_scale", (m,))]
        out += [(f"{tag}_mla.{n}", s) for n, s in (
            ("wdq", (m, ql)), ("q_norm", (ql,)),
            ("wuq", (ql, h * (dn + dr))), ("wdkv", (m, dc + dr)),
            ("kv_norm", (dc,)), ("wuk", (dc, h * dn)),
            ("wuv", (dc, h * dv)), ("wo", (h * dv, m)))]
        out.append((f"{tag}_ln2_scale", (m,)))
        if dense:
            fi = build["d_inner"]
            return out + [(f"{tag}_ffn.{n}", s) for n, s in (
                ("w_gate", (m, fi)), ("w_up", (m, fi)),
                ("w_down", (fi, m)))]
        return out + [(f"{tag}_moe.{n}", s) for n, s in (
            ("router", (m, e)), ("w_gate", (held, m, f)),
            ("w_up", (held, m, f)), ("w_down", (held, f, m)),
            ("s_gate", (m, fs)), ("s_up", (m, fs)), ("s_down", (fs, m)),
            ("router_bias", (1, e)))]

    out = [("emb", (v, m))]
    tags = layer_tags(build)
    for tag, dense in tags[:build["n_layer"]]:
        out += layer(tag, dense)
    out += [("lnf_scale", (m,)), ("head_w", (m, v))]
    for tag, dense in tags[build["n_layer"]:]:
        out += [(f"{tag}_enorm", (m,)), (f"{tag}_hnorm", (m,)),
                (f"{tag}_eh_proj", (2 * m, m))] + layer(tag, dense)
    return out


def rms_norm(x, gain, eps, pr):
    return pr.f(x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
                * gain)


def rope(x, theta: float):
    """x [T, ..., R] rotated at positions 0..T-1, interleaved pairs."""
    t, r = x.shape[0], x.shape[-1]
    inv = float(theta) ** (-jnp.arange(0, r, 2, dtype=F32) / r)
    ang = (jnp.arange(t, dtype=F32)[:, None] * inv).reshape(
        (t,) + (1,) * (x.ndim - 2) + (r // 2,))
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                      a * jnp.sin(ang) + b * jnp.cos(ang)],
                     axis=-1).reshape(x.shape)


def mla(p, x, build, pr, rotate_key=True):
    """One sequence x [T, M] -> [T, M]."""
    t = x.shape[0]
    h, dc = build["n_head"], build["kv_lora_rank"]
    dn, dr, dv = (build["qk_nope_head_dim"], build["qk_rope_head_dim"],
                  build["v_head_dim"])
    eps, theta = build["rms_eps"], build["rope_theta"]
    cq = rms_norm(pr.dot(x, p["wdq"]), p["q_norm"], eps, pr)
    q = pr.dot(cq, p["wuq"]).reshape(t, h, dn + dr)
    q_nope, q_rope = q[..., :dn], pr.f(rope(q[..., dn:], theta))
    ckv = pr.dot(x, p["wdkv"])
    c = rms_norm(ckv[:, :dc], p["kv_norm"], eps, pr)
    kr = ckv[:, dc:]
    if rotate_key:
        kr = pr.f(rope(kr, theta))
    k_nope = pr.dot(c, p["wuk"]).reshape(t, h, dn)
    v = pr.dot(c, p["wuv"]).reshape(t, h, dv)
    scale = float(dn + dr) ** -0.5
    blk = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    s_idx = jnp.arange(t)

    @jax.checkpoint
    def block(t0):
        cut = lambda z: jax.lax.dynamic_slice_in_dim(z, t0, blk)  # noqa
        s = (jnp.einsum("qhd,shd->hqs", pr.m(cut(q_nope)), pr.m(k_nope))
             + jnp.einsum("qhd,sd->hqs", pr.m(cut(q_rope)), pr.m(kr))) \
            * scale
        keep = s_idx[None, :] <= (t0 + jnp.arange(blk))[:, None]
        a = pr.f(jax.nn.softmax(jnp.where(keep[None], pr.f(s), -jnp.inf),
                                axis=-1))
        return jnp.einsum("hqs,shd->qhd", pr.m(a), pr.m(v))

    o = jax.lax.map(block, jnp.arange(0, t, blk)).reshape(t, h * dv)
    return pr.dot(o, p["wo"])


def swiglu(x, w_gate, w_up, w_down, pr):
    return pr.dot(jax.nn.silu(pr.dot(x, w_gate)) * pr.dot(x, w_up), w_down)


def route(p, x, build, pr):
    """(weights [N, K], picks [N, K]) of the router."""
    s = pr.f(jax.nn.sigmoid(x @ p["router"]))
    _, idx = jax.lax.top_k(s + p["router_bias"], build["n_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if build.get("norm_topk_prob", True):
        w = w / jnp.sum(w, -1, keepdims=True)
    return w * build.get("routed_scaling_factor", 1.0), idx


def expert_layer(p, x, build, pr, held=None, shared=True):
    """x [N, M] -> (the held experts' part + the shared expert [N, M],
    the picks per expert over the whole router [E])."""
    start, n_held = held if held is not None else (
        build.get("held_start", 0), build["n_experts_held"])
    w, idx = route(p, x, build, pr)
    load = jnp.sum(idx[:, :, None] == jnp.arange(build["n_routed_experts"]),
                   axis=(0, 1))
    # each token's weight for each held expert, zero where not picked
    wt = jnp.sum(jnp.where(
        idx[:, :, None] == start + jnp.arange(n_held), w[:, :, None], 0.0),
        axis=1)                                                  # [N, E_h]
    xm = pr.m(x)
    hidden = jax.nn.silu(jnp.einsum("nm,emf->nef", xm, pr.m(p["w_gate"]))) \
        * jnp.einsum("nm,emf->nef", xm, pr.m(p["w_up"]))
    y = jnp.einsum("nef,efm->nm", pr.m(hidden * wt[:, :, None]),
                   pr.m(p["w_down"]))
    if shared:
        y = y + swiglu(x, p["s_gate"], p["s_up"], p["s_down"], pr)
    return y, load


@functools.partial(jax.checkpoint, static_argnums=(2, 3, 4, 5))
def _layer(p, x, items, dense, low, rotate_key):
    """One layer over sequences x [B, T, M]: (x, load [E] or None)."""
    build, pr = dict(items), Prec(low)
    eps = build["rms_eps"]
    sub = lambda pre: {k[len(pre):]: v for k, v in p.items()      # noqa
                       if k.startswith(pre)}
    y = jax.vmap(lambda s: mla(sub("mla."), s, build, pr, rotate_key))(
        rms_norm(x, p["ln1_scale"], eps, pr))
    x = x + y
    y = rms_norm(x, p["ln2_scale"], eps, pr)
    b, t, m = y.shape
    if dense:
        f = sub("ffn.")
        return x + swiglu(y, f["w_gate"], f["w_up"], f["w_down"], pr), None
    y, load = expert_layer(sub("moe."), y.reshape(b * t, m), build, pr)
    return x + y.reshape(b, t, m), load


def _items(build: dict) -> tuple:
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in build.items()))


def _ce(x, p, labels, eps, pr):
    """Per-position cross-entropy [B, T] of the shared head."""
    logits = pr.dot(rms_norm(x, p["lnf_scale"], eps, pr), p["head_w"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]


def loss_fn(p: dict, ids, lbl, lbl2, build: dict, low_precision=False,
            mtp_shift=0, rotate_key=True):
    """(loss, (main CE, MTP CE, {layer tag: load [E]})) of sequences
    ids / lbl / lbl2 [B, T] (the tokens, the next ones, the ones after).
    ``mtp_shift`` and ``rotate_key`` build faults on purpose (the MTP
    target one further on; the shared key left unrotated): never the
    reference."""
    pr, items, eps = Prec(low_precision), _items(build), build["rms_eps"]
    layer_of = lambda tag: {k[len(tag) + 1:]: v for k, v in p.items()  # noqa
                            if k.startswith(tag + "_")}
    tags = layer_tags(build)
    x = p["emb"][ids]
    loads = {}
    for tag, dense in tags[:build["n_layer"]]:
        x, load = _layer(layer_of(tag), x, items, dense, pr.low, rotate_key)
        if load is not None:
            loads[tag] = load
    main = jnp.mean(_ce(x, p, lbl, eps, pr))
    mtp = jnp.zeros((), F32)
    for tag, dense in tags[build["n_layer"]:]:
        t = ids.shape[1]
        # position T's next token t_{T+1} is a label, not an input: it
        # rides along behind the causal mask (a whole number of query
        # blocks; its picks count in the layer's load, in the program
        # too) and is cut from the mean
        both = jnp.concatenate(
            [rms_norm(p["emb"][lbl], p[f"{tag}_enorm"], eps, pr),
             rms_norm(x, p[f"{tag}_hnorm"], eps, pr)], axis=-1)
        h, load = _layer(layer_of(tag), pr.dot(both, p[f"{tag}_eh_proj"]),
                         items, dense, pr.low, rotate_key)
        loads[tag] = load
        target = jnp.roll(lbl2, -mtp_shift, axis=1)
        mtp = jnp.mean(_ce(h, p, target, eps, pr)[:, :t - 1])
    return main + build.get("mtp_weight", 0.0) * mtp, (main, mtp, loads)


def bias_after_update(bias, load, gamma: float):
    """b + gamma sign(mean load - load), [1, E]."""
    load = jnp.asarray(load, F32)
    return jnp.asarray(bias, F32) + gamma * jnp.sign(
        jnp.mean(load) - load).reshape(1, -1)


def adam_step(grad, m1, m2, beta1_pow, beta2_pow, build: dict):
    """Adam's change of a parameter, entry by entry in float64, from its
    gradient and the optimizer's state BEFORE the step (the two moments,
    beta1^t and beta2^t): m1' = b1 m1 + (1 - b1) g, m2' = b2 m2 +
    (1 - b2) g^2, -lr sqrt(1 - beta2^t) / (1 - beta1^t) x m1' /
    (sqrt(m2') + epsilon). The hyperparameters are the configuration's,
    not the program's."""
    g = np.asarray(grad, np.float64)
    b1, b2 = build["beta1"], build["beta2"]
    m1 = b1 * np.asarray(m1, np.float64) + (1.0 - b1) * g
    m2 = b2 * np.asarray(m2, np.float64) + (1.0 - b2) * g * g
    b1p, b2p = (np.asarray(x, np.float64).reshape(())
                for x in (beta1_pow, beta2_pow))
    lr_t = build["lr"] * np.sqrt(1.0 - b2p) / (1.0 - b1p)
    return -lr_t * m1 / (np.sqrt(m2) + build["epsilon"])


SAMPLE = 4096


def sample_of(grad):
    """``SAMPLE`` entries of a gradient at evenly strided flat positions
    (all of a smaller one): what two gradients are compared on entry by
    entry, where their norms would agree whatever their directions."""
    flat = grad.reshape(-1)
    return flat[::max(1, flat.shape[0] // SAMPLE)][:SAMPLE]


def loss_and_grad_norms(params: dict, ids, lbl, lbl2, build: dict, which,
                        **faults):
    """(loss, [L2 norm of d loss / d params[role] for role in which],
    {layer tag: the router's bias after one update}, [``sample_of`` each
    of those gradients]). ``params`` may be device arrays of the system
    under test: nothing is copied but the matrices differentiated.
    ``faults``: ``loss_fn``'s ``low_precision``, ``mtp_shift``,
    ``rotate_key``."""
    which = list(which)
    rest = {k: v for k, v in params.items() if k not in which}
    faults = tuple(sorted(faults.items()))

    @functools.partial(jax.jit, static_argnums=(5, 6))
    def run(diff, rest, ids, lbl, lbl2, items, faults):
        build = dict(items)

        def f(diff):
            return loss_fn({**rest, **diff}, ids, lbl, lbl2, build,
                           **dict(faults))
        (loss, (_, _, loads)), grads = jax.value_and_grad(
            f, has_aux=True)(diff)
        return loss, [jnp.sqrt(jnp.sum(jnp.square(grads[r])))
                      for r in which], loads, [sample_of(grads[r])
                                               for r in which]

    with jax.default_matmul_precision("highest"):
        loss, norms, loads, samples = run(
            {r: params[r] for r in which}, rest, jnp.asarray(ids, jnp.int32),
            jnp.asarray(lbl, jnp.int32), jnp.asarray(lbl2, jnp.int32),
            _items(build), faults)
    gamma = build.get("bias_update_gamma", 0.0)
    biases = {tag: np.asarray(bias_after_update(
        params[f"{tag}_moe.router_bias"], load, gamma))
        for tag, load in loads.items()}
    return (float(loss), [float(n) for n in norms], biases,
            [np.asarray(g) for g in samples])
