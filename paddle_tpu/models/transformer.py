"""Transformer-base (reference capability: benchmark/fluid Transformer-base
WMT en-de config named in BASELINE.json; the reference preps it in
benchmark/fluid/models/machine_translation.py-era configs).

The flagship model: encoder-decoder, multi-head attention, pre-norm
residuals. Built entirely from the fluid-style layers so the same program
runs single-chip or sharded (dp × tp) over a mesh — attention/FFN matmuls
are the MXU hot path; paddle_tpu.parallel shards d_model/heads over 'tp' and
batch over 'dp'.
"""

from __future__ import annotations

import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.fluid.initializer import NumpyArrayInitializer
from paddle_tpu.observability import device_scopes as _device_scopes
from paddle_tpu.ops.kv_attention import window_ring


def _const_var(name, value):
    """A non-trainable persistable table (positional encodings, masks)."""
    main = fluid.default_main_program()
    startup = fluid.default_startup_program()
    value = np.asarray(value, dtype=np.float32)
    v = main.global_block().create_var(
        name=name, shape=list(value.shape), dtype="float32",
        persistable=True, stop_gradient=True)
    sv = startup.global_block().create_var(
        name=name, shape=list(value.shape), dtype="float32", persistable=True)
    NumpyArrayInitializer(value)(sv, startup.global_block())
    return v


def position_encoding(max_len, d_model):
    pos = np.arange(max_len)[:, None].astype(np.float64)
    i = np.arange(d_model // 2)[None, :].astype(np.float64)
    angle = pos / np.power(10000.0, 2 * i / d_model)
    enc = np.zeros((max_len, d_model))
    enc[:, 0::2] = np.sin(angle)
    enc[:, 1::2] = np.cos(angle)
    return enc.astype(np.float32)


def multi_head_attention(q_in, kv_in, d_model, n_head, dropout, mask=None,
                         fused=False, causal=False, name=""):
    # (a merged-QKV projection variant was measured on v5e and REJECTED:
    # 42.9 vs 39.6 ms/step — the split's copies eat the bigger-matmul
    # win; see docs/performance.md transformer accounting)
    d_k = d_model // n_head
    if fused:
        # the fused block expresses causality via `causal`; an additive
        # mask would be silently ignored — fail loudly (ValueError, not
        # assert: must survive python -O)
        if mask is not None:
            raise ValueError(
                "fused attention takes causal=True, not an additive mask")
        # ONE fused op spanning the projections AND the attention dots
        # (layers.fused_multi_head_attention → ops/attention_block.py):
        # its custom VJP is spelled so no [B,T,H,D]↔[B,H,T,D] relayout
        # ever materializes, forward or backward — the composed bthd
        # graph still paid ~7.4 ms/step of backward-grad relayouts on
        # Transformer-base bs128 (docs/performance.md accounting). With
        # an sp mesh axis the op falls back to ring/Ulysses sequence-
        # parallel attention. Attention-weight dropout runs inside
        # (hash-derived keep mask regenerated in the backward), matching
        # the unfused graph's softmax→dropout→matmul semantics.
        return layers.fused_multi_head_attention(
            q_in, kv_in, d_model, n_head, causal=causal,
            dropout_prob=dropout)

    q = layers.fc(q_in, size=d_model, num_flatten_dims=2, bias_attr=False)
    k = layers.fc(kv_in, size=d_model, num_flatten_dims=2, bias_attr=False)
    v = layers.fc(kv_in, size=d_model, num_flatten_dims=2, bias_attr=False)

    def split_heads(x):
        # [B, L, D] -> [B, H, L, dk]
        r = layers.reshape(x, shape=[0, 0, n_head, d_k])
        return layers.transpose(r, perm=[0, 2, 1, 3])

    q, k, v = split_heads(q), split_heads(k), split_heads(v)
    q = layers.scale(q, scale=d_k ** -0.5)
    logits = layers.matmul(q, k, transpose_y=True)   # [B, H, Lq, Lk]
    if mask is not None:
        logits = layers.elementwise_add(logits, mask)
    weights = layers.softmax(logits)
    if dropout:
        weights = layers.dropout(weights, dropout_prob=dropout,
                                 dropout_implementation="upscale_in_train")
    ctx = layers.matmul(weights, v)                  # [B, H, Lq, dk]
    ctx = layers.transpose(ctx, perm=[0, 2, 1, 3])
    ctx = layers.reshape(ctx, shape=[0, 0, d_model])
    return layers.fc(ctx, size=d_model, num_flatten_dims=2, bias_attr=False)


def ffn(x, d_model, d_inner, dropout):
    h = layers.fc(x, size=d_inner, num_flatten_dims=2, act="relu")
    if dropout:
        h = layers.dropout(h, dropout_prob=dropout,
                           dropout_implementation="upscale_in_train")
    return layers.fc(h, size=d_model, num_flatten_dims=2)


def _residual(x, sub, dropout):
    if dropout:
        sub = layers.dropout(sub, dropout_prob=dropout,
                             dropout_implementation="upscale_in_train")
    return layers.elementwise_add(x, sub)


def encoder_layer(x, d_model, d_inner, n_head, dropout, fused=False):
    attn_in = layers.layer_norm(x, begin_norm_axis=2)
    attn = multi_head_attention(attn_in, attn_in, d_model, n_head, dropout,
                                fused=fused)
    x = _residual(x, attn, dropout)
    ffn_in = layers.layer_norm(x, begin_norm_axis=2)
    return _residual(x, ffn(ffn_in, d_model, d_inner, dropout), dropout)


def decoder_layer(x, enc_out, causal_mask, d_model, d_inner, n_head,
                  dropout, fused=False):
    self_in = layers.layer_norm(x, begin_norm_axis=2)
    self_attn = multi_head_attention(
        self_in, self_in, d_model, n_head, dropout,
        mask=None if fused else causal_mask, fused=fused, causal=fused)
    x = _residual(x, self_attn, dropout)
    cross_in = layers.layer_norm(x, begin_norm_axis=2)
    cross = multi_head_attention(cross_in, enc_out, d_model, n_head, dropout,
                                 fused=fused)
    x = _residual(x, cross, dropout)
    ffn_in = layers.layer_norm(x, begin_norm_axis=2)
    return _residual(x, ffn(ffn_in, d_model, d_inner, dropout), dropout)


def transformer(src_ids, tgt_ids, src_vocab, tgt_vocab, max_len,
                d_model=512, d_inner=2048, n_head=8, n_layer=6,
                dropout=0.1, fused_attention=False, name="transformer",
                project=True):
    pe = _const_var(name + "_pos_enc",
                    position_encoding(max_len, d_model))
    # causal mask [1, 1, L, L]: -1e9 above the diagonal
    causal = np.triu(np.full((max_len, max_len), -1e9, np.float32), k=1)
    causal_mask = _const_var(name + "_causal_mask",
                             causal[None, None, :, :])

    def embed(ids, vocab, scope):
        emb = layers.embedding(
            ids, size=[vocab, d_model],
            param_attr=fluid.ParamAttr(
                name=f"{name}_{scope}_emb",
                initializer=fluid.initializer.Normal(0.0, d_model ** -0.5)))
        emb = layers.scale(emb, scale=d_model ** 0.5)
        return layers.elementwise_add(emb, pe, axis=1)

    enc = embed(src_ids, src_vocab, "src")
    if dropout:
        enc = layers.dropout(enc, dropout_prob=dropout,
                             dropout_implementation="upscale_in_train")
    for _ in range(n_layer):
        enc = encoder_layer(enc, d_model, d_inner, n_head, dropout,
                            fused=fused_attention)
    enc = layers.layer_norm(enc, begin_norm_axis=2)

    dec = embed(tgt_ids, tgt_vocab, "tgt")
    if dropout:
        dec = layers.dropout(dec, dropout_prob=dropout,
                             dropout_implementation="upscale_in_train")
    for _ in range(n_layer):
        dec = decoder_layer(dec, enc, causal_mask, d_model, d_inner, n_head,
                            dropout, fused=fused_attention)
    dec = layers.layer_norm(dec, begin_norm_axis=2)
    if not project:
        # caller fuses the vocab projection into the loss
        # (layers.fused_linear_cross_entropy)
        return dec
    return layers.fc(dec, size=tgt_vocab, num_flatten_dims=2,
                     bias_attr=False)


# ---------------------------------------------------------------------------
# The hybrid sparse block of the slot views (decoder_lm(..., **arch)):
# RMSNorm, a mixer per layer of kind "gqa" (softmax attention with
# grouped KV heads, through the paged pool; no positions unless
# ``gqa_rope_theta``; an output gate where ``gqa_gate``), "swa" (a
# "gqa" layer over a sliding window, with rotary positions, in a page
# group of its own), "kda" (Kimi Delta
# Attention, a fixed-size recurrent state per slot), "gdn" (Gated
# DeltaNet: the same delta rule with one decay a head, a state that is
# not square and a chunked prefill), "ssd" (Mamba-2's
# state-space dual: another fixed-size state per slot, a chunked scan as
# its prefill), "s6" (Mamba-1's selective scan: a decay per channel AND
# state index, a normed low-rank step, no matrix form), "conv" (the LFM2
# family's gated short convolution: a
# third fixed-size state per slot, the last rows of one elementwise
# product) or "mla" (latent attention with rotary positions and the
# DSA indexer's sparse selection: a latent plane and an indexer-key plane
# in the paged pool), and an expert layer of which this program holds a
# share — or, in the first ``first_k_dense`` layers (all of them, in a
# dense model), a dense SwiGLU layer of width ``d_inner``. One scope serves the prefill and the decode view:
# every weight and every state variable is named.
# ---------------------------------------------------------------------------

_HYBRID_KEYS = {
    # the period of layer kinds, cycled over n_layer
    "layer_kinds": None,
    # "gqa" layers: KV heads, the size of a head, the output gate, a
    # norm of every q and k head. "swa" layers are "gqa" layers that
    # attend the last ``window`` positions alone, with rotary positions
    # (``rope_theta``), cached in a page group of their own
    # (True; "projection": ONE norm over the whole q and the whole k
    # projection, Olmo's)
    "n_kv_head": None, "head_dim": None, "gqa_gate": True,
    "qk_norm": False, "window": None,
    # rotary positions on the "gqa" layers themselves (rotate-half, all
    # of a head's dimensions, this base; None: no positions)
    "gqa_rope_theta": None,
    # a geometry that differs by kind: the "swa" layers' own KV heads
    # (None: n_kv_head), a value head of another size than a key head
    # (both kinds; None: head_dim), the leading share of a head that the
    # rotation turns (both kinds; None: all of it), what multiplies V
    # before it is cached, and a learned logit a query head that joins a
    # window layer's softmax and carries no value
    "swa_n_kv_head": None, "gqa_v_head_dim": None, "rotary_dim": None,
    "value_scale": None, "swa_sink": False,
    # what multiplies the attention scores ("gqa" layers) in place of
    # head_dim ** -0.5
    "attn_scale": None,
    # a norm AFTER each sub-layer too (x + Norm(f(Norm(x)))), and a
    # factor on the embedding, on each sub-layer's result before it
    # joins the residual (x + r f(Norm(x))) and under the logits
    # (logits / logits_scale); a head that is the embedding's own table
    # ``pre_norms`` False with ``post_norms`` is Olmo's reordered norm:
    # x + Norm(f(x)), none before a sub-layer
    "post_norms": False, "pre_norms": True,
    "embed_scale": 1.0, "residual_scale": 1.0,
    "logits_scale": 1.0, "tie_embeddings": False,
    # "kda" layers
    "kda_heads": None, "kda_head_dim": None, "kda_conv_taps": 4,
    "kda_gate_rank": None,
    # "gdn" layers: heads, a key's and a value's size (the state is
    # [key, value] a head), the conv's taps, the prefill's chunk
    "gdn_heads": None, "gdn_key_dim": None, "gdn_value_dim": None,
    "gdn_conv_taps": 4, "gdn_chunk": 64,
    # "ssd" layers: heads, a head's channels, the state's size, the
    # groups that share B and C, the conv's taps, the prefill's chunk
    "ssd_heads": None, "ssd_head_dim": None, "ssd_d_state": None,
    "ssd_groups": 1, "ssd_conv_taps": 4, "ssd_chunk": None,
    # "s6" layers: the inner width (channels), the state's size, the
    # rank the step passes through, the conv's taps, the rows a turn of
    # the prefill's scan walks
    "s6_d_inner": None, "s6_d_state": None, "s6_dt_rank": None,
    "s6_conv_taps": 4, "s6_chunk": 64,
    # "conv" layers: the depthwise conv's taps (the cache keeps taps - 1)
    "conv_taps": None,
    # "mla" layers: the query's and the cache's latent widths, a head's
    # parts, the rotation's base, the indexer's heads and its top-k
    "q_lora_rank": None, "kv_lora_rank": None, "qk_nope_head_dim": None,
    "qk_rope_head_dim": None, "v_head_dim": None, "rope_theta": None,
    "index_n_heads": None, "index_head_dim": None, "index_topk": None,
    # leading layers whose FFN is dense (of decoder_lm's d_inner); the
    # router's correction bias (picks by score + bias, weights by score)
    "first_k_dense": 0, "router_bias": False,
    # the expert layer: the router's width, the experts held here (from
    # held_start), the picks per token, an expert's width
    "n_routed_experts": None, "n_experts_held": None, "held_start": 0,
    "n_experts_per_tok": None, "d_expert": None, "n_shared_experts": 1,
    "norm_topk_prob": True, "routed_scaling_factor": 1.0,
    # the shared expert's own width (n_shared_experts * d_expert when
    # None) and how the router scores: "sigmoid" over every expert, or
    # "softmax_topk" (the best by logit, a softmax over the picks)
    "d_shared": None, "scoring": "sigmoid",
    "rms_eps": 1e-5, "dtype": "float32"}

# the sizes only layers of one kind read: required where the kind occurs
_KIND_KEYS = {
    "gqa": ("n_kv_head", "head_dim"),
    "swa": ("n_kv_head", "head_dim", "window", "rope_theta"),
    "kda": ("kda_heads", "kda_head_dim", "kda_gate_rank"),
    "gdn": ("gdn_heads", "gdn_key_dim", "gdn_value_dim"),
    "ssd": ("ssd_heads", "ssd_head_dim", "ssd_d_state", "ssd_chunk"),
    "s6": ("s6_d_inner", "s6_d_state", "s6_dt_rank"),
    "conv": ("conv_taps",),
    "mla": ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "rope_theta",
            "index_n_heads", "index_head_dim", "index_topk")}
# the DSA indexer's sizes: all three None is a latent layer WITHOUT an
# indexer (no index plane, no selection: every earlier position attended)
_INDEXER_KEYS = ("index_n_heads", "index_head_dim", "index_topk")


# the expert layer's sizes: read by no one where every layer's
# feed-forward is dense (``first_k_dense`` >= n_layer)
_EXPERT_KEYS = ("n_routed_experts", "n_experts_held", "n_experts_per_tok",
                "d_expert")
# sizes whose None is a value (the op's own default), not an omission
_OPTIONAL = ("attn_scale", "d_shared", "gqa_rope_theta", "swa_n_kv_head",
             "gqa_v_head_dim", "rotary_dim", "value_scale")


def hybrid_arch(arch: dict, mode: str, n_layer: int, n_head=None) -> dict:
    """The hybrid block's sizes, checked: every key of ``_HYBRID_KEYS``,
    the ones whose default is None required (a kind's own only where
    ``layer_kinds`` has the kind). ``kinds`` is the layer kind of each
    of the ``n_layer`` layers."""
    unknown = sorted(set(arch) - set(_HYBRID_KEYS))
    if unknown:
        raise TypeError(f"decoder_lm: unknown argument(s) {unknown}; the "
                        f"hybrid block takes {sorted(_HYBRID_KEYS)}")
    hy = {**_HYBRID_KEYS, **arch}
    period = tuple(hy["layer_kinds"] or ())
    bad = sorted(set(period) - set(_KIND_KEYS))
    if bad or not period:
        raise ValueError(f"layer_kinds {period}: a layer is one of "
                         f"{sorted(_KIND_KEYS)}")
    unused = {k for keys in _KIND_KEYS.values() for k in keys} \
        - {k for kind in period for k in _KIND_KEYS[kind]}
    dense_only = hy["first_k_dense"] >= n_layer
    if dense_only:
        unused |= set(_EXPERT_KEYS)
    no_indexer = hy["index_topk"] is None
    missing = sorted(k for k, v in hy.items()
                     if v is None and k not in unused
                     and k not in _OPTIONAL
                     and not (no_indexer and k in _INDEXER_KEYS))
    if missing:
        raise ValueError(f"decoder_lm: a hybrid block (layer_kinds given) "
                         f"needs {missing} too")
    if mode == "full":
        # whole sequences in, logits out: no pool, no page table. The
        # view a trainer differentiates and the oracle runs
        other = sorted(set(period) - {"mla"})
        if other:
            raise ValueError(
                f"decoder_lm mode 'full' with layer kinds {other}: they "
                f"are served by the slot views prefill_paged and "
                f"decode_paged alone; of the hybrid block's kinds only "
                f"'mla' has a full view (ops/mla.py:mla_full)")
        if not no_indexer:
            raise ValueError(
                "decoder_lm mode 'full' with index_topk: the full view "
                "of a latent layer attends every earlier position; a "
                "layer with the DSA indexer has none yet")
    elif mode not in ("prefill_paged", "decode_paged"):
        raise ValueError(
            f"decoder_lm mode {mode!r} with layer_kinds: the hybrid block "
            f"is served by the slot views prefill_paged and decode_paged "
            f"alone (a verify window would have to roll a recurrent state "
            f"back)")
    elif no_indexer and "mla" in period:
        raise ValueError(
            f"decoder_lm mode {mode!r} with 'mla' layers and no "
            f"index_topk: the paged latent ops select through the DSA "
            f"indexer; a latent layer without one has the full view alone")
    if hy["attn_scale"] is not None and "swa" in period:
        raise ValueError("attn_scale is read by 'gqa' layers alone: a "
                         "window layer scales by head_dim ** -0.5")
    _check_grouped_geometry(hy, period, n_head)
    if not hy["pre_norms"] and not hy["post_norms"]:
        raise ValueError("pre_norms False without post_norms: a sub-layer "
                         "has a norm before it, after it, or both")
    if not dense_only and not 0 < hy["n_experts_held"] \
            <= hy["n_routed_experts"] - hy["held_start"]:
        raise ValueError("n_experts_held must lie inside the router's "
                         "n_routed_experts from held_start")
    hy["kinds"] = tuple(period[i % len(period)] for i in range(n_layer))
    return hy


def _check_grouped_geometry(hy, period, n_head):
    """What the grouped kinds' per-kind geometry has to satisfy."""
    grouped = {"gqa", "swa"} & set(period)
    set_keys = sorted(k for k in ("swa_n_kv_head", "gqa_v_head_dim",
                                  "rotary_dim", "value_scale")
                      if hy[k] is not None)
    if set_keys and not grouped:
        raise ValueError(f"{set_keys} are read by 'gqa' and 'swa' layers "
                         f"alone; layer_kinds {period} has neither")
    if (hy["swa_n_kv_head"] is not None or hy["swa_sink"]) \
            and "swa" not in period:
        raise ValueError("swa_n_kv_head and swa_sink are a window layer's: "
                         f"layer_kinds {period} has no 'swa'")
    if not grouped:
        return
    rot = hy["rotary_dim"]
    if rot is not None:
        if rot % 2 or not 0 < rot <= hy["head_dim"]:
            raise ValueError(f"rotary_dim {rot}: the rotated share of a "
                             f"head is even and at most head_dim "
                             f"{hy['head_dim']}")
        if not (hy["gqa_rope_theta"] or "swa" in period):
            raise ValueError("rotary_dim without a rotation: no 'swa' "
                             "layer and no gqa_rope_theta")
    if n_head is not None:
        for kind, key in (("gqa", "n_kv_head"), ("swa", "swa_n_kv_head")):
            n_kv = hy[key] if hy[key] is not None else hy["n_kv_head"]
            if kind in period and (n_kv < 1 or n_head % n_kv):
                raise ValueError(f"{key} {n_kv} does not divide n_head "
                                 f"{n_head} ('{kind}' layers)")


def hybrid_weight_std(name: str, shape) -> float:
    """The standard deviation a hybrid block's weight matrix is drawn
    with (the program's start-up and the benchmark's drawer use this one
    rule): 1 for the embedding (nothing scales it and an RMSNorm follows),
    taps**-0.5 for the depthwise conv (its output keeps its input's
    variance), 0.1 for a conv's bias, 0.01 for a router's correction bias (small beside the
    scores' spread, so that picking and weighing differ), 1 for a window layer's sink logits (a trained one is O(1)
    beside scores of O(1)), Glorot's sqrt(2 / (fan_in + fan_out)) over the last two
    dimensions otherwise (an "s6" layer's ``w_x`` and ``w_dt`` among them:
    no suffix of that kind has a rule of its own; its ``a_log`` is kept
    flat so that no drawer of matrices takes it for one)."""
    if name.endswith("_emb") or name.endswith(".sink"):
        return 1.0
    if name.endswith(".conv"):
        return float(shape[0]) ** -0.5
    if name.endswith(".conv_bias"):
        return 0.1
    if name.endswith(".router_bias"):
        return 0.01
    return (2.0 / (float(shape[-2]) + float(shape[-1]))) ** 0.5


class _HybridBlock:
    """What the hybrid block's views share: the embedding, one layer,
    the final norm and the head, under the names one scope serves them
    all by. ``loads`` (a trainer's list) collects, per expert layer with
    a correction bias, (the bias parameter, the layer's ``Load``
    output): what ``router_bias_update`` reads after the step."""

    def __init__(self, hy, mode, name, vocab, d_model, d_inner, n_head,
                 pool_var=None, pools=None, feeds=None, loads=None):
        self.hy, self.mode, self.name, self.vocab = hy, mode, name, vocab
        self.d_model, self.d_inner, self.n_head = d_model, d_inner, n_head
        self.pool_var, self.pools, self.feeds = pool_var, pools, feeds
        self.loads = loads
        self.init = _HybridNormal()    # every matrix, by name and shape

    def pa(self, pname, matrix=False):
        return fluid.ParamAttr(name=f"{self.name}_{pname}",
                               initializer=self.init if matrix else None)

    def embed(self, ids):
        hy = self.hy
        x = layers.embedding(ids, size=[self.vocab, self.d_model],
                             dtype=hy["dtype"],
                             param_attr=self.pa("emb", True))
        if hy["embed_scale"] != 1.0:
            x = layers.scale(x, scale=float(hy["embed_scale"]))
        return x

    def logits(self, x, out_name=None):
        """The final norm and the head over flat rows x [N, M]: float32
        logits [N, V], in a variable named ``out_name`` where an engine
        is to fetch them."""
        hy = self.hy
        x = layers.rms_norm(x, hy["rms_eps"], self.pa("lnf_scale"))
        head = {}
        if hy["tie_embeddings"]:
            # ONE table, read by the embedding and by the logits
            head["weight"] = fluid.default_main_program().global_block()\
                .var(f"{self.name}_emb")
        if hy["logits_scale"] != 1.0:
            head["scale"] = 1.0 / float(hy["logits_scale"])
        return layers.dense(
            x, self.vocab,
            None if hy["tie_embeddings"] else self.pa("head_w", True),
            out_dtype="float32", out_name=out_name, **head)

    def layer(self, x, i, kind, tag=None, dense_ffn=False):
        """Layer ``i`` of ``kind`` over x: mixer and feed-forward, each
        behind its norm and joined to the residual. Its weights are
        named ``<name>_<tag>_...`` (``l<i>`` unless ``tag`` is given: a
        module beside the stack, the trainer's MTP layer)."""
        return _hybrid_layer(self, x, i, kind, tag or f"l{i}", dense_ffn)


def _hybrid_layer(blk, x, i, kind, tag, dense_ffn):
    hy, mode, name = blk.hy, blk.mode, blk.name
    d_model, d_inner, n_head = blk.d_model, blk.d_inner, blk.n_head
    pool_var, pools, feeds, init, pa = (blk.pool_var, blk.pools, blk.feeds,
                                        blk.init, blk.pa)
    prefill, full = mode == "prefill_paged", mode == "full"
    dt, eps = hy["dtype"], hy["rms_eps"]
    n_slots = None if full else pools["n_slots"]

    def join(x, y):
        """x + residual_scale * y."""
        if hy["residual_scale"] != 1.0:
            y = layers.scale(y, scale=float(hy["residual_scale"]))
        return layers.elementwise_add(x, y)

    def grouped_counts(i):
        """A prefill of more than ``DENSE_MAX_TOKENS`` tokens takes the
        expert layer's grouped way and counts the rows it held, in a
        counter of the prefill views' own (the engine runs the decode
        view's startup, which knows nothing of it, and makes the
        counter itself: ``SlotGenerativeModel._grouped_counters``). A
        view of up to that many tokens has none, and its program is
        what it was."""
        from paddle_tpu.ops.expert_ffn import DENSE_MAX_TOKENS
        if not prefill or pools["prompt_len"] <= DENSE_MAX_TOKENS:
            return {}
        return {"counts": pool_var(f"{name}_moe_grouped_{i}",
                                   [2, hy["n_experts_held"]], "int32")}

    def before(x, which):
        """The norm before a sub-layer, where the block has one."""
        if not hy["pre_norms"]:
            return x
        return layers.rms_norm(x, eps, pa(f"{tag}_{which}_scale"))

    y = before(x, "ln1")
    if full:
        sizes = {k: hy[k] for k in _KIND_KEYS["mla"]
                 if k != "rope_theta" and k not in _INDEXER_KEYS}
        sizes["n_head"] = n_head
        y = layers.mla_full(y, d_model, sizes, f"{name}_{tag}_mla", init,
                            hy["rope_theta"], eps)
    elif kind in ("gqa", "swa"):
        # a window layer's pools are its group's (fewer pages,
        # another table: serving/kv_pool.py "Window group")
        swa = kind == "swa"
        d = hy["head_dim"]
        # a kind's own KV heads; K rows of n_kv * d, V rows of n_kv * dv
        n_kv = hy["swa_n_kv_head"] if swa and hy["swa_n_kv_head"] \
            else hy["n_kv_head"]
        dv = hy["gqa_v_head_dim"] or d
        plane = "page_w" if swa else "page_"
        shape = [pools["window_pages"] if swa else pools["shape"][0],
                 pools["shape"][1]]
        pk = pool_var(f"{name}_{plane}k_{i}", shape + [n_kv * d],
                      pools["dtype"])
        pv = pool_var(f"{name}_{plane}v_{i}", shape + [n_kv * dv],
                      pools["dtype"])
        pks = pvs = None
        if pools["codec"] == "int8":
            sshape = shape + [n_kv]
            pks = pool_var(f"{name}_{plane}ks_{i}", sshape)
            pvs = pool_var(f"{name}_{plane}vs_{i}", sshape)
        gqa = dict(n_kv_head=n_kv, head_dim=d, v_head_dim=dv,
                   gate=hy["gqa_gate"], qk_norm=hy["qk_norm"],
                   rms_eps=eps, attn_scale=hy["attn_scale"],
                   rotary_dim=hy["rotary_dim"],
                   value_scale=hy["value_scale"])
        if swa:
            gqa.update(window=hy["window"], rope_theta=hy["rope_theta"],
                       sink=hy["swa_sink"],
                       attended_name=f"{name}_l{i}_attn_attended")
        elif hy["gqa_rope_theta"]:
            gqa.update(rope_theta=hy["gqa_rope_theta"])
        attr = pa(f"l{i}_attn", True)    # the base of its names
        if prefill:
            y = layers.kv_attention_prefill_paged(
                y, feeds["page_rows_w" if swa else "page_rows"],
                d_model, n_head, pk, pv, pks, pvs,
                codec=pools["codec"], param_attr=attr, gqa=gqa)
        else:
            y = layers.kv_attention_decode_paged(
                y, feeds["page_table_w" if swa else "page_table"],
                feeds["pos"], feeds["seq_len"],
                feeds["gen_start"], feeds["active"], d_model, n_head,
                pk, pv, pks, pvs, codec=pools["codec"],
                param_attr=attr, gqa=gqa)
    elif kind == "mla":
        from paddle_tpu.ops.mla import latent_width
        sizes = {k: hy[k] for k in _KIND_KEYS["mla"]
                 if k != "rope_theta"}
        sizes["n_head"] = n_head
        wide = latent_width(hy["kv_lora_rank"], hy["qk_rope_head_dim"])
        pc = pool_var(f"{name}_page_c_{i}", pools["shape"] + [wide],
                      pools["dtype"])
        pi = pool_var(f"{name}_page_i_{i}",
                      pools["shape"] + [hy["index_head_dim"]],
                      pools["dtype"])
        y = layers.mla(
            y, pc, pi, d_model, sizes, f"{name}_l{i}_mla", init,
            hy["rope_theta"], eps,
            **(dict(rows=feeds["page_rows"]) if prefill else dict(
                decode=[feeds[k] for k in (
                    "page_table", "pos", "seq_len", "gen_start",
                    "active", "position")],
                selected_name=f"{name}_l{i}_mla_selected")))
    elif kind == "ssd":
        sizes = {k: hy[k] for k in _HYBRID_KEYS if k.startswith("ssd_")}
        inner = hy["ssd_heads"] * hy["ssd_head_dim"]
        state = pool_var(f"{name}_ssd_state_{i}",
                         [n_slots, hy["ssd_d_state"], inner])
        conv = pool_var(
            f"{name}_ssd_conv_{i}",
            [n_slots, hy["ssd_conv_taps"] - 1,
             inner + 2 * hy["ssd_groups"] * hy["ssd_d_state"]], dt)
        y = layers.ssd(
            y, state, conv, d_model, sizes, f"{name}_l{i}_ssd", init,
            eps,
            **(dict(seq_len=feeds["seq_len"], slot=feeds["state_slot"])
               if prefill else dict(active=feeds["active"])))
    elif kind == "s6":
        sizes = {k: hy[k] for k in _HYBRID_KEYS if k.startswith("s6_")}
        state = pool_var(f"{name}_s6_state_{i}",
                         [n_slots, hy["s6_d_state"], hy["s6_d_inner"]])
        conv = pool_var(
            f"{name}_s6_conv_{i}",
            [n_slots, hy["s6_conv_taps"] - 1, hy["s6_d_inner"]], dt)
        y = layers.s6(
            y, state, conv, d_model, sizes, f"{name}_l{i}_s6", init, eps,
            **(dict(seq_len=feeds["seq_len"], slot=feeds["state_slot"])
               if prefill else dict(active=feeds["active"])))
    elif kind == "gdn":
        sizes = {k: hy[k] for k in _HYBRID_KEYS if k.startswith("gdn_")}
        h, dk, dv = (hy[k] for k in _KIND_KEYS["gdn"])
        state = pool_var(f"{name}_gdn_state_{i}", [n_slots, h, dk, dv])
        conv = pool_var(
            f"{name}_gdn_conv_{i}",
            [n_slots, hy["gdn_conv_taps"] - 1, 2 * h * dk + h * dv], dt)
        y = layers.gdn(
            y, state, conv, d_model, sizes, f"{name}_l{i}_gdn", init, eps,
            **(dict(seq_len=feeds["seq_len"], slot=feeds["state_slot"])
               if prefill else dict(active=feeds["active"])))
    elif kind == "conv":
        conv = pool_var(f"{name}_conv_state_{i}",
                        [n_slots, hy["conv_taps"] - 1, d_model], dt)
        y = layers.shortconv(
            y, conv, d_model, hy["conv_taps"], f"{name}_l{i}_conv", init,
            **(dict(seq_len=feeds["seq_len"], slot=feeds["state_slot"])
               if prefill else dict(active=feeds["active"])))
    else:
        h, d = hy["kda_heads"], hy["kda_head_dim"]
        state = pool_var(f"{name}_kda_state_{i}", [n_slots, h, d, d])
        conv = pool_var(f"{name}_kda_conv_{i}",
                        [n_slots, hy["kda_conv_taps"] - 1, 3 * h * d],
                        dt)
        y = layers.kda(
            y, state, conv, d_model, h, d, f"{name}_l{i}_kda", init,
            hy["kda_gate_rank"], hy["kda_conv_taps"], eps,
            **(dict(seq_len=feeds["seq_len"], slot=feeds["state_slot"])
               if prefill else dict(active=feeds["active"])))
    if hy["post_norms"]:
        y = layers.rms_norm(y, eps, pa(f"{tag}_ln1_post_scale"))
    x = join(x, y)
    y = before(x, "ln2")
    if dense_ffn:
        y = layers.swiglu_ffn(y, d_model, d_inner, f"{name}_{tag}_ffn",
                              init)
    else:
        if full:
            # every token is real; a trainer asks for the step's load
            told = dict(load=blk.loads is not None
                        and hy["router_bias"])
        elif prefill:
            told = dict(seq_len=feeds["seq_len"], **grouped_counts(i))
        else:
            told = dict(valid=feeds["active"],
                        counts=pool_var(f"{name}_moe_counts_{i}",
                                        [2, hy["n_experts_held"]],
                                        "int32"))
        y = layers.expert_ffn_held(
            y, d_model, hy["d_expert"], hy["n_routed_experts"],
            hy["n_experts_held"], hy["n_experts_per_tok"],
            f"{name}_{tag}_moe", init, hy["held_start"],
            hy["n_shared_experts"],
            hy["norm_topk_prob"], hy["routed_scaling_factor"],
            **told,
            router_bias=hy["router_bias"], d_shared=hy["d_shared"],
            scoring=hy["scoring"])
        if isinstance(y, tuple):
            y, load = y
            blk.loads.append((f"{name}_{tag}_moe.router_bias", load))
    if hy["post_norms"]:
        y = layers.rms_norm(y, eps, pa(f"{tag}_ln2_post_scale"))
    x = join(x, y)
    return x


def _hybrid_body(hy, mode, x_ids, name, vocab, d_model, d_inner, n_head,
                 pool_var, pools, feeds):
    """Embedding, the layers and the head of a hybrid view; returns the
    float32 logits, flat: [1, V] at the prompt's true end for the
    prefill, [n_slots, V] for the decode step, [B * T, V] for the full
    view. The logits variable is named ``<name>_logits`` so that an
    engine can be asked to fetch it."""
    blk = _HybridBlock(hy, mode, name, vocab, d_model, d_inner, n_head,
                       pool_var, pools, feeds)
    x = blk.embed(x_ids)
    for i, kind in enumerate(hy["kinds"]):
        x = blk.layer(x, i, kind, dense_ffn=i < hy["first_k_dense"])
    x = layers.reshape(x, shape=[-1, d_model])
    if mode == "prefill_paged":
        one = layers.fill_constant([1, 1], "int64", 1)
        x = layers.gather(x, layers.elementwise_sub(feeds["seq_len"], one))
    return blk.logits(x, out_name=f"{name}_logits")


class _HybridNormal(fluid.initializer.Initializer):
    """Normal(0, hybrid_weight_std(name, shape)) for whatever matrix it
    is asked to initialise, drawn elementwise (``hash_normal_random``: a
    3.3 B-parameter start-up then needs no memory beside its outputs)."""

    def __call__(self, var, block):
        fluid.initializer.HashNormalInitializer(
            hybrid_weight_std(var.name, var.shape))(var, block)


# ---------------------------------------------------------------------------
# Decoder-only LM serving family (paddle_tpu/serving): one set of weights,
# several program views that share every parameter NAME so a single scope
# serves them all —
#   "full"         — logits over the whole sequence via causal fused
#                    attention: the full-forward-per-token baseline (and
#                    the parity oracle).
#   "prefill_paged" — the in-flight-batching prefill: ONE request
#                    (batch 1) whose K/V rows are scattered into the
#                    per-layer [n_pages, page_size, H*D] page pools
#                    through per-position flat row indices (sentinel =
#                    shared-prefix skip); fetches the first generated
#                    token, sampled on-device (layers.token_sample).
#   "decode_paged" — one decode step over the WHOLE slot pool: a fully
#                    static [n_slots]-row program (free slots ride along
#                    masked) that resolves reads/writes through a
#                    [n_slots, max_pages] page-table feed and samples
#                    each row's next token on-device. This is the
#                    executable the in-flight scheduler re-dispatches
#                    forever (ISSUE 9, 17). FLAGS_kv_cache_codec stores
#                    the pages as bf16/int8.
#   "decode_verify_paged" — the speculative-decoding verify step
#                    (ISSUE 19): score a [n_slots, K+1] token window
#                    (last committed token + K drafts) in ONE causal
#                    dispatch over the paged pool and sample every
#                    window position on-device. The engine's
#                    draft→verify→commit loop re-dispatches this
#                    executable instead of decode_paged, committing up
#                    to K+1 tokens per step.
# Every parameter is explicitly named (LayerHelper's auto names are
# globally unique, so cross-program sharing REQUIRES explicit names).
# ---------------------------------------------------------------------------

def decoder_lm(mode: str, prompt_len: int = 16, max_new: int = 16,
               vocab: int = 64, d_model: int = 32, d_inner: int = 64,
               n_head: int = 2, n_layer: int = 2, name: str = "lm",
               cache_len=None, n_slots=None, page_size=None,
               n_pages=None, kv_codec=None, spec_k=None, **arch):
    """Emit the `mode` view ("full" | "prefill_paged" | "decode_paged"
    | "decode_verify_paged") of the decoder-only LM into the current
    default programs. ``cache_len`` decouples the cache size from this
    view's prompt bucket (ladder prefills at P < P_max address the same
    full-length slots); the paged modes need ``n_slots``. The paged
    views (ISSUE 17) keep K/V in [n_pages, page_size, H*D] page pools
    behind a per-slot page-table feed — ``page_size`` must divide
    cache_len (the decode gather then covers exactly cache_len logical
    rows); ``n_pages`` defaults to every slot at full length
    (n_slots * cache_len / page_size); ``kv_codec`` defaults to
    FLAGS_kv_cache_codec ('none' | 'bf16' | 'int8' storage). Returns
    (output_var, feed_specs) — logits for full, the on-device-sampled
    next token for the paged views.

    The verify views (ISSUE 19) take ``spec_k`` (default 4): K drafted
    tokens per step, scored together with the last committed token as a
    [n_slots, K+1] window — one fixed-shape executable per (n_slots,
    spec_k), sampling all K+1 window positions on-device so the host's
    accept rule is a pure comparison.

    ``arch`` (``layer_kinds=...`` and the sizes :func:`hybrid_arch`
    lists) turns the block into that of a hybrid sparse model: RMSNorm,
    per layer a grouped-KV softmax mixer ("gqa", through the same paged
    ops and pools, without positions unless ``gqa_rope_theta``; "swa"
    over a sliding window),
    a Kimi Delta Attention mixer ("kda", a fixed-size recurrent state
    per slot beside the pages), a Gated DeltaNet mixer ("gdn": the same
    delta rule with one decay a head, a state [key, value] a head,
    prefilled chunk by chunk), a Mamba-2 state-space mixer ("ssd",
    another fixed-size state, prefilled by a chunked scan), a Mamba-1
    selective-scan mixer ("s6": a decay per channel and state index,
    prefilled row by row with the state on the chip), a gated
    short convolution ("conv", a window of its last rows per slot) or
    latent attention with rotary positions and the DSA indexer's sparse
    selection ("mla": a latent plane and an indexer-key plane in the
    pool, and a ``position`` feed of the decode view), and an expert
    layer of which this program holds a share (a dense SwiGLU layer of
    ``d_inner`` in the first ``first_k_dense`` layers). Without it the
    views are the multi-head ReLU family's, unchanged."""
    hy = hybrid_arch(arch, mode, n_layer, n_head) if arch else None
    # all geometry validation + defaulting lives in ONE record shared
    # with the cross-view family verifier (analysis/contracts.py) —
    # the view consumes the normalized constants instead of re-deriving
    from paddle_tpu.analysis.contracts import validate_geometry
    geom = validate_geometry(mode, prompt_len, max_new,
                             cache_len=cache_len, n_slots=n_slots,
                             page_size=page_size, n_pages=n_pages,
                             kv_codec=kv_codec, spec_k=spec_k)
    cache_len = geom.cache_len
    spec_k = geom.spec_k
    page_size = geom.page_size
    n_pages = geom.n_pages
    max_pages = geom.max_pages
    kv_codec = geom.kv_codec
    store_dt = geom.store_dtype
    d_k = d_model // n_head
    main = fluid.default_main_program()
    startup = fluid.default_startup_program()
    main._geometry = geom              # family verifier cross-checks this
    if hy is None:
        pe = _const_var(name + "_pos_enc",
                        position_encoding(cache_len, d_model))

    def attn_pa(i):
        return fluid.ParamAttr(name=f"{name}_l{i}_attn")

    def pa(pname):
        return fluid.ParamAttr(name=f"{name}_{pname}")

    # pool caches: persistable in main (read+written by the slot ops —
    # donated state), zero-filled by startup. The startup fills are
    # DEFERRED to after the whole net is built: rng is salted per
    # startup-op index, so parameter initializers must sit at the same
    # indices in every mode's startup for the views to share weights.
    _pool_fills = []

    def pool_var(pname, shape, dtype="float32"):
        v = main.global_block().create_var(
            name=pname, shape=shape, dtype=dtype,
            persistable=True, stop_gradient=True)
        _pool_fills.append((pname, shape, dtype))
        return v

    def fill_pools():
        # startup pool fills go AFTER every param initializer (rng-salt
        # stability across modes — see pool_var above)
        from paddle_tpu.fluid.initializer import ConstantInitializer
        for pname, shape, fdt in _pool_fills:
            sv = startup.global_block().create_var(
                name=pname, shape=shape, dtype=fdt, persistable=True)
            ConstantInitializer(0.0)(sv, startup.global_block())

    def sdata(nm, shape, dtype="int64"):
        # the slot views' feeds are fully static (no batch dimension)
        return layers.data(name=nm, shape=shape, dtype=dtype,
                           append_batch_size=False)

    page_rows = page_table = state_slot = position = None
    page_rows_w = page_table_w = None
    pos = gen_start = active = sample_step = None
    if mode == "decode_paged":
        S = int(n_slots)
        tok = sdata("tok", [S, 1, 1])
        pos = sdata("pos", [S, 1])
        seq_len = sdata("seq_len", [S, 1])
        gen_start = sdata("gen_start", [S, 1])
        active = sdata("active", [S, 1])
        seed_in = sdata("seed", [S, 1])
        sample_step = sdata("sample_step", [S, 1])
        temp = sdata("temperature", [S, 1], "float32")
        top_k = sdata("top_k", [S, 1])
        feed_specs = {"tok": ([S, 1, 1], "int64"),
                      "pos": ([S, 1], "int64"),
                      "seq_len": ([S, 1], "int64"),
                      "gen_start": ([S, 1], "int64"),
                      "active": ([S, 1], "int64"),
                      "seed": ([S, 1], "int64"),
                      "sample_step": ([S, 1], "int64"),
                      "temperature": ([S, 1], "float32"),
                      "top_k": ([S, 1], "int64")}
        # the slot -> page indirection rides in as a STATIC-shape
        # feed: any admission/release/page mix dispatches the same
        # executable (sentinel entries point one past the pool)
        page_table = sdata("page_table", [S, max_pages])
        feed_specs["page_table"] = ([S, max_pages], "int64")
        if hy is not None and "mla" in hy["kinds"]:
            # each slot's token's TRUE position (the rotation's): pos is
            # a ROW of the slot, and generated rows start at the bucket
            position = sdata("position", [S, 1])
            feed_specs["position"] = ([S, 1], "int64")
        if hy is not None and "swa" in hy["kinds"]:
            # the window group's table: a slot's RING of pages
            ring = window_ring(hy["window"], page_size)
            page_table_w = sdata("page_table_w", [S, ring])
            feed_specs["page_table_w"] = ([S, ring], "int64")
        x_ids, t = tok, 1
    elif mode == "decode_verify_paged":
        S = int(n_slots)
        k1 = int(spec_k) + 1
        # the window feed: position 0 the row's last committed token,
        # 1..K the drafts. The sampling feeds are PER WINDOW POSITION
        # ([S, K+1]): sample_step[b, i] = gen_count[b] + i, so window
        # position i consumes exactly the (seed, step) noise draw the
        # sequential engine would at that step — the losslessness
        # guarantee (docs/serving.md 'Speculative decoding')
        tok = sdata("tok", [S, k1, 1])
        pos = sdata("pos", [S, 1])
        seq_len = sdata("seq_len", [S, 1])
        gen_start = sdata("gen_start", [S, 1])
        active = sdata("active", [S, 1])
        win_len = sdata("win_len", [S, 1])
        seed_in = sdata("seed", [S, k1])
        sample_step = sdata("sample_step", [S, k1])
        temp = sdata("temperature", [S, k1], "float32")
        top_k = sdata("top_k", [S, k1])
        feed_specs = {"tok": ([S, k1, 1], "int64"),
                      "pos": ([S, 1], "int64"),
                      "seq_len": ([S, 1], "int64"),
                      "gen_start": ([S, 1], "int64"),
                      "active": ([S, 1], "int64"),
                      "win_len": ([S, 1], "int64"),
                      "seed": ([S, k1], "int64"),
                      "sample_step": ([S, k1], "int64"),
                      "temperature": ([S, k1], "float32"),
                      "top_k": ([S, k1], "int64")}
        page_table = sdata("page_table", [S, max_pages])
        feed_specs["page_table"] = ([S, max_pages], "int64")
        x_ids, t = tok, k1
    elif mode == "prefill_paged":
        # one request at a time joins the pool (batch 1, static)
        t = prompt_len
        ids = sdata("ids", [1, t, 1])
        seq_len = sdata("seq_len", [1, 1])
        seed_in = sdata("seed", [1, 1])
        temp = sdata("temperature", [1, 1], "float32")
        top_k = sdata("top_k", [1, 1])
        feed_specs = {"ids": ([1, t, 1], "int64"),
                      "seq_len": ([1, 1], "int64"),
                      "seed": ([1, 1], "int64"),
                      "temperature": ([1, 1], "float32"),
                      "top_k": ([1, 1], "int64")}
        # flat pool row per prompt position from the page lease —
        # sentinel rows skip prefix-shared pages (already resident)
        page_rows = sdata("page_rows", [t, 1])
        feed_specs["page_rows"] = ([t, 1], "int64")
        if hy is not None and "swa" in hy["kinds"]:
            # the window group's row per prompt position: the sentinel
            # for every position behind the first decode step's window
            page_rows_w = sdata("page_rows_w", [t, 1])
            feed_specs["page_rows_w"] = ([t, 1], "int64")
        if hy is not None and {"kda", "gdn", "ssd", "s6", "conv"} \
                & set(hy["kinds"]):
            # which slot's recurrent state this request's prompt lands
            # in (>= n_slots: nowhere — the warm-up's dispatch)
            state_slot = sdata("state_slot", [1, 1])
            feed_specs["state_slot"] = ([1, 1], "int64")
        x_ids = ids
    else:
        t = cache_len
        ids = layers.data(name="ids", shape=[t, 1], dtype="int64")
        feed_specs = {"ids": ([-1, t, 1], "int64")}
        x_ids = ids

    if hy is not None and mode == "full":
        logits = _hybrid_body(hy, mode, x_ids, name, vocab, d_model,
                              d_inner, n_head, None, None, None)
        return layers.reshape(logits, shape=[-1, t, vocab]), feed_specs
    if hy is not None:
        logits = _hybrid_body(
            hy, mode, x_ids, name, vocab, d_model, d_inner, n_head, pool_var,
            pools=dict(shape=[n_pages, page_size], dtype=store_dt,
                       codec=kv_codec, n_slots=int(n_slots),
                       prompt_len=int(prompt_len),
                       # every slot's ring, whatever the context
                       window_pages=int(n_slots) * window_ring(
                           hy["window"], page_size)
                       if "swa" in hy["kinds"] else 0),
            feeds=dict(seq_len=seq_len, page_rows=page_rows,
                       state_slot=state_slot, page_table=page_table,
                       pos=pos, gen_start=gen_start, active=active,
                       position=position, page_rows_w=page_rows_w,
                       page_table_w=page_table_w))
        fill_pools()
        if mode == "prefill_paged":
            sample_step = layers.fill_constant([1, 1], "int64", 0)
        tok_out = layers.token_sample(logits, temp, top_k, seed_in,
                                      sample_step)
        return tok_out, feed_specs

    emb = layers.embedding(x_ids, size=[vocab, d_model],
                           param_attr=pa("emb"))
    x = layers.scale(emb, scale=d_model ** 0.5)
    if mode == "decode_paged":
        # semantic position of this token for row b is
        # seq_len[b] + generated-so-far = seq_len + (pos - gen_start)
        # (prompts are right-padded to their bucket; the cache ROW is
        # storage only, the mask orders attention)
        gen = layers.elementwise_sub(pos, gen_start)
        pos_ids = layers.elementwise_add(seq_len, gen)
        pe_t = layers.gather(pe, pos_ids)                  # [B, M]
        pe_t = layers.reshape(pe_t, shape=[-1, 1, d_model])
        x = layers.elementwise_add(x, pe_t)
    elif mode == "decode_verify_paged":
        # semantic position of window position i for row b is
        # seq_len[b] + (pos[b] + i - gen_start[b]) — and since
        # sample_step[b, i] = (pos - gen_start + 1) + i that is exactly
        # seq_len + sample_step - 1, computed from the feeds in-program
        sl = layers.expand(seq_len, expand_times=[1, k1])   # [S, K1]
        one = layers.fill_constant([S, k1], "int64", 1)
        off = layers.elementwise_sub(sample_step, one)
        pos_ids = layers.elementwise_add(sl, off)           # [S, K1]
        pe_t = layers.gather(pe, pos_ids)                  # [S*K1, M]
        pe_t = layers.reshape(pe_t, shape=[-1, k1, d_model])
        x = layers.elementwise_add(x, pe_t)
    elif t != cache_len:
        pe_t = layers.slice(pe, axes=[0], starts=[0], ends=[t])
        x = layers.elementwise_add(x, pe_t, axis=1)
    else:
        x = layers.elementwise_add(x, pe, axis=1)

    for i in range(n_layer):
        attn_in = layers.layer_norm(x, begin_norm_axis=2,
                                    param_attr=pa(f"l{i}_ln1_scale"),
                                    bias_attr=pa(f"l{i}_ln1_bias"))
        if mode == "full":
            attn = layers.fused_multi_head_attention(
                attn_in, attn_in, d_model, n_head, causal=True,
                param_attr=attn_pa(i))
        else:
            # the whole model width on the minor dimension: row-major
            # at rest on the TPU, a page contiguous (ops/kv_attention
            # .py:_paged_pools has why [.., n_head, d_k] was not)
            pshape = [n_pages, page_size, n_head * d_k]
            pk = pool_var(f"{name}_page_k_{i}", pshape, store_dt)
            pv = pool_var(f"{name}_page_v_{i}", pshape, store_dt)
            pks = pvs = None
            if kv_codec == "int8":
                sshape = [n_pages, page_size, n_head]
                pks = pool_var(f"{name}_page_ks_{i}", sshape)
                pvs = pool_var(f"{name}_page_vs_{i}", sshape)
            if mode == "prefill_paged":
                attn = layers.kv_attention_prefill_paged(
                    attn_in, page_rows, d_model, n_head, pk, pv,
                    pks, pvs, codec=kv_codec, param_attr=attn_pa(i))
            elif mode == "decode_verify_paged":
                attn = layers.kv_attention_verify_paged(
                    attn_in, page_table, pos, seq_len, gen_start,
                    active, win_len, d_model, n_head, pk, pv, pks,
                    pvs, codec=kv_codec, param_attr=attn_pa(i))
            else:
                attn = layers.kv_attention_decode_paged(
                    attn_in, page_table, pos, seq_len, gen_start,
                    active, d_model, n_head, pk, pv, pks, pvs,
                    codec=kv_codec, param_attr=attn_pa(i))
        x = layers.elementwise_add(x, attn)
        ffn_in = layers.layer_norm(x, begin_norm_axis=2,
                                   param_attr=pa(f"l{i}_ln2_scale"),
                                   bias_attr=pa(f"l{i}_ln2_bias"))
        h = layers.fc(ffn_in, size=d_inner, num_flatten_dims=2,
                      act="relu", param_attr=pa(f"l{i}_ffn1_w"),
                      bias_attr=pa(f"l{i}_ffn1_b"))
        h = layers.fc(h, size=d_model, num_flatten_dims=2,
                      param_attr=pa(f"l{i}_ffn2_w"),
                      bias_attr=pa(f"l{i}_ffn2_b"))
        x = layers.elementwise_add(x, h)

    x = layers.layer_norm(x, begin_norm_axis=2,
                          param_attr=pa("lnf_scale"),
                          bias_attr=pa("lnf_bias"))
    logits = layers.fc(x, size=vocab, num_flatten_dims=2,
                       param_attr=pa("head_w"), bias_attr=False)

    fill_pools()

    if mode == "prefill_paged":
        # first generated token, sampled on-device from the logits row
        # at the prompt's true end (batch 1: flatten [1,P,V] -> [P,V])
        flat = layers.reshape(logits, shape=[-1, vocab])
        one = layers.fill_constant([1, 1], "int64", 1)
        last_idx = layers.elementwise_sub(seq_len, one)
        last = layers.gather(flat, last_idx)               # [1, V]
        zero = layers.fill_constant([1, 1], "int64", 0)
        tok_out = layers.token_sample(last, temp, top_k, seed_in, zero)
        return tok_out, feed_specs
    if mode in ("decode_paged", "decode_verify_paged"):
        # decode: [S, V] flat. Verify samples EVERY window position
        # on-device ([S*K1, V] flat): row b*K1+i is the token the
        # sequential engine would emit at step sample_step[b, i] given
        # the window's prefix — the host accept rule is then a pure
        # token comparison against the drafts
        flat = layers.reshape(logits, shape=[-1, vocab])
        tok_out = layers.token_sample(flat, temp, top_k, seed_in,
                                      sample_step)
        return tok_out, feed_specs
    return logits, feed_specs


def build_decoder_lm_programs(prompt_len: int = 16, max_new: int = 16,
                              vocab: int = 64, d_model: int = 32,
                              d_inner: int = 64, n_head: int = 2,
                              n_layer: int = 2, name: str = "lm",
                              seed: int = 7, modes=("full",),
                              prompt_buckets=None, n_slots=None,
                              page_size=None, n_pages=None,
                              kv_codec=None, spec_k=None, **arch):
    """The serving program family: {key: (main, startup, feed_specs,
    fetch_name)}. All mains share every parameter name — run ONE startup
    (any of them; their parameter initializers are identical) into a
    scope and it serves every view alike.

    ``modes`` defaults to the one view that needs no slot geometry, the
    ``full`` oracle; the slot server's are :func:`slot_modes`.
    ``prompt_buckets`` (ascending lengths, largest == prompt_len) emits
    one prefill view PER bucket — keys ``prefill_paged@P``, with the
    bare mode name aliased to the largest bucket. ``n_slots`` sizes
    the decode slot pool of the paged views; ``page_size``/``n_pages``/
    ``kv_codec`` shape the page pool (ISSUE 17 — see decoder_lm);
    ``spec_k`` sizes the verify window of the ``decode_verify_paged``
    view (ISSUE 19). ``arch`` (``layer_kinds=...`` and the sizes of
    :func:`hybrid_arch`) makes the slot views those of a hybrid sparse
    model; see :func:`decoder_lm`."""
    cache_len = prompt_len + max_new
    buckets = tuple(sorted(set(int(b)
                               for b in (prompt_buckets or (prompt_len,)))))
    if buckets[-1] != prompt_len:
        raise ValueError(f"largest prompt bucket {buckets[-1]} must "
                         f"equal prompt_len {prompt_len}")
    cfg = dict(max_new=max_new, vocab=vocab, d_model=d_model,
               d_inner=d_inner, n_head=n_head, n_layer=n_layer,
               name=name, cache_len=cache_len, n_slots=n_slots,
               page_size=page_size, n_pages=n_pages, kv_codec=kv_codec,
               spec_k=spec_k, **arch)
    out = {}

    def emit(key, mode, p_len):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = seed
        startup.random_seed = seed
        with fluid.program_guard(main, startup):
            outv, feed_specs = decoder_lm(mode, prompt_len=p_len, **cfg)
        main._is_test = True
        out[key] = (main, startup, feed_specs, outv.name)

    for mode in modes:
        if mode == "prefill_paged":
            for p in buckets:
                emit(f"{mode}@{p}", mode, p)
            out[mode] = out[f"{mode}@{buckets[-1]}"]
        else:
            emit(mode, mode, prompt_len)
    return out


def slot_modes(layout="paged", spec=False):
    """The slot engine's program modes: pass the result as ``modes=`` to
    :func:`build_decoder_lm_programs` and hand the programs to
    :func:`paddle_tpu.serving.engine.make_slot_model`. ``spec=True``
    adds the speculative-decode verify view (ISSUE 19) — the engine
    discovers it by key and switches step() to draft→verify→commit.
    ``layout`` is vestigial (ROADMAP.md Design): the benchmark's runner
    passes its configuration's ``"paged"``, the only layout there is."""
    if layout != "paged":
        raise ValueError(
            f"slot_modes: KV layout {layout!r} is not 'paged' — the "
            f"contiguous slot layout was removed at PR 29, the paged "
            f"pool is the slot server's only KV layout")
    modes = ("prefill_paged", "decode_paged")
    return modes + ("decode_verify_paged",) if spec else modes


def contracts_lint_family():
    """``proglint --contracts`` default target: the full decoder_lm
    serving family (every mode, bucketed prefills, full + paged + verify
    views) at lint-sized dims — the cross-view contract verifier
    (analysis/contracts.py) runs over what this returns."""
    from paddle_tpu.analysis.contracts import DECODER_LM_MODES
    return build_decoder_lm_programs(
        prompt_len=8, max_new=8, vocab=32, d_model=16, d_inner=32,
        n_head=2, n_layer=2, prompt_buckets=(4, 8), n_slots=4, spec_k=3,
        modes=DECODER_LM_MODES)


def serve_lint_prefill_paged():
    """proglint --module entry: the in-flight-batching prefill that
    scatters one request's K/V through its page-table lease (shared-
    prefix rows dropped via sentinel — ISSUE 17)."""
    decoder_lm("prefill_paged", n_slots=4)


def serve_lint_decode_paged():
    """proglint --module entry: the slot-pool decode step with on-device
    token sampling (the in-flight scheduler's executable) — page-table
    feed indirection, donated page pools (the proglint --memory
    target)."""
    decoder_lm("decode_paged", n_slots=4)


def serve_lint_verify_paged():
    """proglint --module entry: the speculative-decode verify step —
    [n_slots, K+1] window, on-device sampling of every window position,
    window writes resolved through the page-table feed, beyond-lease
    rows dropped via sentinel (ISSUE 19)."""
    decoder_lm("decode_verify_paged", n_slots=4)


def build_lm(seq_len: int, vocab: int, d_model: int, d_inner: int,
             n_head: int, n_layer: int, name: str = "lm",
             mtp_layers: int = 0, mtp_weight: float = 0.3,
             bias_update_gamma: float = 1e-3, lr: float = 2.2e-4,
             beta1: float = 0.9, beta2: float = 0.95,
             epsilon: float = 1e-8, **arch):
    """The hybrid block's TRAINING graph, beside :func:`build` (the
    encoder-decoder's): ``decoder_lm``'s ``full`` view of ``n_layer``
    layers (``arch``: :func:`hybrid_arch`'s sizes) over ``ids``
    [B, seq_len, 1], next-token cross-entropy against ``lbl_ids``, and
    with ``mtp_layers`` 1 the multi-token-prediction module of
    DeepSeek-V3 (arXiv:2412.19437, section 2.2) in the loss:

        h'_i = W_eh [RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(h_i)]
        one more layer (the period's kind, an expert layer), the final
        norm, the head and the table SHARED with the main model, target
        t_{i+2} (``lbl2_ids``), over the positions i < seq_len (the last
        one's next token is a label, not an input: it is computed for
        the shapes' sake and left out of the mean)

    loss = mean CE + ``mtp_weight`` x mean MTP CE. Adam on every
    parameter but the routers' correction biases, which no gradient
    reaches: after the optimizer's ops ``router_bias_update`` moves each
    by ``bias_update_gamma`` against the step's load. The MTP module's
    ops carry the device scope ``mtp``. Returns (loss, the per-layer
    accumulated loads' variable names, feed_specs)."""
    if mtp_layers not in (0, 1):
        raise ValueError("build_lm: mtp_layers is 0 or 1 (config.json's "
                         "num_nextn_predict_layers)")
    hy = hybrid_arch(arch, "full", n_layer)
    t = int(seq_len)
    ids = layers.data(name="ids", shape=[t, 1], dtype="int64")
    lbl = layers.data(name="lbl_ids", shape=[t, 1], dtype="int64")
    feed_specs = {"ids": ([-1, t, 1], "int64"),
                  "lbl_ids": ([-1, t, 1], "int64")}
    main = fluid.default_main_program()
    block = main.global_block()
    loads = []
    blk = _HybridBlock(hy, "full", name, vocab, d_model, d_inner, n_head,
                       loads=loads)

    def ce(x, labels):
        """Per-position cross-entropy [B * T, 1] of the shared head."""
        return layers.softmax_with_cross_entropy(
            blk.logits(layers.reshape(x, shape=[-1, d_model])),
            layers.reshape(labels, shape=[-1, 1]))

    x = blk.embed(ids)
    for i, kind in enumerate(hy["kinds"]):
        x = blk.layer(x, i, kind, dense_ffn=i < hy["first_k_dense"])
    loss = layers.mean(ce(x, lbl))
    if mtp_layers:
        lbl2 = layers.data(name="lbl2_ids", shape=[t, 1], dtype="int64")
        feed_specs["lbl2_ids"] = ([-1, t, 1], "int64")
        first_mtp_op = len(block.ops)
        eps = hy["rms_eps"]
        both = layers.concat(
            [layers.rms_norm(blk.embed(lbl), eps, blk.pa("mtp0_enorm")),
             layers.rms_norm(x, eps, blk.pa("mtp0_hnorm"))], axis=2)
        h = layers.dense(both, d_model, blk.pa("mtp0_eh_proj", True))
        h = blk.layer(h, n_layer, hy["kinds"][-1], tag="mtp0")
        per = layers.reshape(ce(h, lbl2), shape=[-1, t])
        mtp_loss = layers.mean(layers.slice(per, axes=[1], starts=[0],
                                            ends=[t - 1]))
        for op in block.ops[first_mtp_op:]:
            op.desc.attrs[_device_scopes.LAYER_SCOPE_ATTR] = "mtp"
        loss = layers.elementwise_add(
            loss, layers.scale(mtp_loss, scale=float(mtp_weight)))
    fluid.optimizer.Adam(learning_rate=lr, beta1=beta1, beta2=beta2,
                         epsilon=epsilon).minimize(loss)
    totals = [layers.router_bias_update(
        block.var(bias_name), load, float(bias_update_gamma),
        total_name=bias_name.replace(".router_bias", ".load"))
        for bias_name, load in loads]
    return loss, totals, feed_specs


def build(is_train: bool = True, src_vocab: int = 32000,
          tgt_vocab: int = 32000, max_len: int = 128, d_model: int = 512,
          d_inner: int = 2048, n_head: int = 8, n_layer: int = 6,
          dropout: float = 0.1, lr: float = 1e-4, warmup: int = 4000,
          label_smooth_eps: float = 0.1, fused_attention: bool = False,
          fused_head: bool = False, lr_scheduler: str = "const"):
    """Transformer-base training graph (Vaswani config: 512/2048/8/6).

    fused_head routes the loss through layers.fused_linear_cross_entropy
    (Pallas streaming kernel — the [N, V] logits never reach HBM). Off by
    default for training: XLA's composed path runs the two grad matmuls
    off the SAVED logits at ~peak MXU, so the kernel's recompute tax
    outweighs its traffic savings at base dims (measured 47.8 vs 41.8
    ms/step, bs128 v5e); it wins forward-only and when logits memory is
    the constraint (large N·V)."""
    src = layers.data(name="src_ids", shape=[max_len, 1], dtype="int64")
    tgt = layers.data(name="tgt_ids", shape=[max_len, 1], dtype="int64")
    lbl = layers.data(name="lbl_ids", shape=[max_len, 1], dtype="int64")
    flat_label = layers.reshape(lbl, shape=[-1, 1])
    eps = label_smooth_eps if is_train else 0.0
    if fused_head:
        # fused loss head: vocab projection + label-smoothed CE in one
        # Pallas kernel — the [N, V] logits (0.5 GB bf16 at bs128) never
        # reach HBM (layers.fused_linear_cross_entropy)
        dec = transformer(src, tgt, src_vocab, tgt_vocab, max_len, d_model,
                          d_inner, n_head, n_layer,
                          dropout if is_train else 0.0,
                          fused_attention=fused_attention, project=False)
        flat_dec = layers.reshape(dec, shape=[-1, d_model])
        loss_vec = layers.fused_linear_cross_entropy(
            flat_dec, flat_label, tgt_vocab, label_smoothing=eps)
    else:
        logits = transformer(src, tgt, src_vocab, tgt_vocab, max_len,
                             d_model, d_inner, n_head, n_layer,
                             dropout if is_train else 0.0,
                             fused_attention=fused_attention)
        flat_logits = layers.reshape(logits, shape=[-1, tgt_vocab])
        # closed-form smoothing inside the CE op (no [N, V] one-hot
        # materialization — at V=32k the one_hot+label_smooth+soft CE
        # chain cost several full-width HBM passes)
        loss_vec = layers.softmax_with_cross_entropy(
            flat_logits, flat_label,
            label_smoothing=eps) if eps else \
            layers.softmax_with_cross_entropy(flat_logits, flat_label)
    loss = layers.mean(loss_vec)
    if is_train:
        if lr_scheduler == "noam":
            # the Vaswani schedule: lr * d_model^-0.5 * min(n^-0.5,
            # n * warmup^-1.5). NOTE: under "noam", `lr` is the Noam
            # MULTIPLIER (conventionally ~1.0-2.0), not an absolute
            # rate — the default 1e-4 would freeze training at ~7e-8
            if lr < 1e-2:
                raise ValueError(
                    f"lr_scheduler='noam' interprets lr as the Noam "
                    f"multiplier (use ~1.0); lr={lr} would give a peak "
                    f"rate of ~{lr * d_model ** -0.5 * warmup ** -0.5:.1e}")
            from paddle_tpu.fluid.learning_rate_scheduler import noam_decay
            rate = noam_decay(d_model, warmup, learning_rate=lr)
        elif lr_scheduler == "const":
            rate = lr
        else:
            raise ValueError(
                f"unknown lr_scheduler {lr_scheduler!r} "
                f"(expected 'const' or 'noam')")
        fluid.optimizer.Adam(learning_rate=rate, beta1=0.9,
                             beta2=0.997, epsilon=1e-9).minimize(loss)
    feed_specs = {"src_ids": ([-1, max_len, 1], "int64"),
                  "tgt_ids": ([-1, max_len, 1], "int64"),
                  "lbl_ids": ([-1, max_len, 1], "int64")}
    return loss, [], feed_specs
