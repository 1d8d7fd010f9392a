"""Serving engines: the per-model execution layer under the server.

Two engine kinds, one discipline — every runtime dispatch lands on a
shape signature that was WARMED (compiled or AOT-loaded) at startup, so
steady-state serving performs zero XLA compilations
(``serving.metrics.forbid_compiles`` turns the contract into an error;
``paddle_serving_compilations_total`` is the witness):

- :class:`ServedModel` — one-shot inference over a ``save_inference_model``
  directory: a :class:`~paddle_tpu.inference.predictor.PaddlePredictor`
  with one AOT executable per batch-bucket feed signature
  (``save_compiled``/``load_compiled`` per bucket — the multi-signature
  persistence satellite), requests padded to the nearest bucket and
  sliced back (serving/bucketing.py).

- :class:`SlotGenerativeModel` — in-flight batched decoding (ISSUE 9)
  over a PAGED KV pool (ISSUE 17): the decode executable is ONE
  fixed-shape ``[n_slots]``-row program; requests JOIN a free slot
  mid-flight (prefill scatters their cache rows in) and LEAVE on
  EOS/max-tokens/cancel, so the device stays saturated with whatever
  work exists right now — no batch barrier, with on-device
  temperature/top-k sampling per slot. Slots address their cache
  through a per-slot page table into one shared ``[n_pages, page_size,
  H*D]`` pool, admission is gated by FREE PAGES for the request's span
  (prompt bucket + token budget) instead of a whole worst-case row,
  and requests with a common prompt prefix physically share full
  prefix pages through a refcounted radix tree
  (``serving/kv_pool.py``). The page table is a fixed-shape
  ``[n_slots, max_pages]`` feed, so join/leave churn never re-lowers.
  ``make_slot_model`` builds it. Autoregressive serving is a prefill
  per admission + O(1)-per-token decode steps instead of a fresh full
  forward per token; its base class :class:`GenerativeModel` holds the
  scope and the dispatch door and, over a family's ``full`` view, is
  the greedy oracle that full forward is kept for.
"""

from __future__ import annotations

import collections
import hashlib
import json
import os
import pickle
import re
import time
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from paddle_tpu.observability import device_scopes
from paddle_tpu.observability import runtime as obs_runtime
from paddle_tpu.observability import trace_context as tctx
from paddle_tpu.serving import bucketing, kv_pool
from paddle_tpu.serving import metrics as smetrics
from paddle_tpu.utils import padding as _padding


class PromptTooLongError(ValueError):
    """Typed admission rejection: the prompt exceeds the model's prompt
    bucket (carried over the wire as kind='bad_request')."""


# -- AOT executable persistence (the slot engine's; the predictor has
# the same discipline inline) ---------------------------------------------

def _sha256_file(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def save_executable(path: str, lowered) -> bool:
    """Serialize a lowered+compiled executable with a sha256 sidecar.
    Returns False, with a warning that names the error, when the
    backend does not round-trip executable serialization."""
    try:
        from jax.experimental import serialize_executable as se
        payload = se.serialize(lowered.compile())
        with open(path, "wb") as f:
            pickle.dump(payload, f)
        with open(path + ".sha256", "w") as f:
            f.write(_sha256_file(path))
        return True
    except Exception as e:
        import warnings
        warnings.warn(f"AOT executable {path} was not persisted: "
                      f"{type(e).__name__}: {e}", stacklevel=2)
        return False


def load_executable(path: str, devices):
    """Deserialize an executable saved by :func:`save_executable`, to
    run on ``devices`` (the ones it was compiled for: left to its
    default the loader hands it every local device, and each call then
    fails on a host with several); None on any mismatch/corruption
    (caller falls back to the compile path).
    SECURITY: pickle — the directory must be a trusted model dir, same
    trust level as the model program itself (see predictor.py)."""
    if not os.path.exists(path):
        return None
    digest_path = path + ".sha256"
    if os.path.exists(digest_path):
        with open(digest_path) as f:
            want = f.read().strip()
        if _sha256_file(path) != want:
            import warnings
            warnings.warn(f"AOT executable {path} failed its integrity "
                          f"check — ignoring it", stacklevel=2)
            return None
    try:
        from jax.experimental import serialize_executable as se
        with open(path, "rb") as f:
            payload = pickle.load(f)
        return se.deserialize_and_load(*payload,
                                       execution_devices=list(devices))
    except Exception as e:
        import warnings
        warnings.warn(f"AOT executable {path} did not load: "
                      f"{type(e).__name__}: {e}", stacklevel=2)
        return None


class _Loaded:
    """An engine's executables loaded ahead of time (its ``_aot``
    table, shared): they dispatch in place of the blocks' own, so where
    an engine has them ``observability.device_scopes`` reads its
    programs' names from them. Holds nothing else of the engine."""

    def __init__(self, table: dict):
        self._table = table
        device_scopes.register(self)

    def device_executables(self) -> list:
        return list(self._table.values())


class ServedModel:
    """A saved inference model behind the bucket discipline.

    ``warmup()`` loads (or compiles and persists) one AOT executable per
    batch bucket; ``infer()`` pads a request batch to the nearest bucket,
    dispatches, and slices the padded rows back off every output."""

    def __init__(self, name: str, model_dir: str,
                 policy: Optional[bucketing.BucketPolicy] = None,
                 config=None):
        from paddle_tpu.inference import AnalysisConfig, PaddlePredictor
        self.name = name
        self.model_dir = model_dir
        self.policy = policy or bucketing.BucketPolicy()
        if config is None:
            config = AnalysisConfig(model_dir=model_dir)
        config.model_tag = name
        self.predictor = PaddlePredictor(config)
        self._warmed: set = set()      # padded feed-shape signatures
        block = self.predictor._program.desc.global_block
        self.row_specs: Dict[str, Tuple[Tuple[int, ...], str]] = {}
        for fname in self.predictor.get_input_names():
            v = block.var(fname)
            self.row_specs[fname] = (tuple(int(d) for d in v.shape[1:]),
                                     v.dtype or "float32")

    # -- warmup ----------------------------------------------------------
    def _example_feeds(self, batch: int) -> Dict[str, np.ndarray]:
        return {n: np.zeros((batch,) + shape, dtype=np.dtype(dtype))
                for n, (shape, dtype) in self.row_specs.items()}

    def _shape_sig(self, feeds) -> Tuple:
        return tuple(sorted((n, tuple(np.shape(v)), str(
            np.asarray(v).dtype)) for n, v in feeds.items()))

    def warmup(self, aot_dir: Optional[str] = None,
               persist: bool = True) -> Dict[str, int]:
        """Warm every bucket: load its AOT executable from disk when
        present, else compile (counted in
        paddle_serving_compilations_total) and, with ``persist``,
        serialize it next to the model so the NEXT process boots every
        bucket without a compiler invocation. Returns
        {"loaded": k, "compiled": m}."""
        aot_dir = aot_dir or self.model_dir
        self.predictor.load_compiled(aot_dir)
        loaded = compiled = 0
        for bucket in self.policy.batch_buckets:
            feeds = self._example_feeds(bucket)
            sig = self._shape_sig(feeds)
            if self.predictor.has_aot_for(feeds):
                loaded += 1
            else:
                smetrics.count_compile(self.name, "bucket")
                compiled += 1
                persisted = False
                if persist:
                    try:
                        self.predictor.save_compiled(aot_dir, feeds)
                        self.predictor.load_compiled(aot_dir)
                        # check THIS bucket's executable specifically —
                        # load_compiled returning True only says some
                        # signature loaded
                        persisted = self.predictor.has_aot_for(feeds)
                    except Exception:
                        persisted = False
                if not persisted:
                    # backend without executable serialization: warm the
                    # JIT executable cache instead (still zero compiles
                    # at steady state — the signature is now resident)
                    self.predictor.run(feeds)
            self._warmed.add(sig)
        return {"loaded": loaded, "compiled": compiled}

    # -- dispatch --------------------------------------------------------
    def infer(self, feeds: Dict[str, np.ndarray]) -> List[np.ndarray]:
        """Pad-and-slice inference: n rows in, n rows out, executed on
        bucket-shaped executables only. Oversized batches are chunked by
        the largest bucket."""
        n_total = int(np.shape(feeds[next(iter(feeds))])[0])
        chunks = self.policy.chunks(n_total)
        outs_per_chunk: List[List[np.ndarray]] = []
        row0 = 0
        for chunk_rows in chunks:
            chunk = {n: np.asarray(v)[row0:row0 + chunk_rows]
                     for n, v in feeds.items()}
            row0 += chunk_rows
            bucket = self.policy.bucket_for(chunk_rows)
            padded, n = bucketing.pad_to_bucket(
                chunk, bucket, batch_names=list(chunk))
            sig = self._shape_sig(padded)
            if sig not in self._warmed:
                # an unwarmed signature compiles here — counted, and a
                # hard error under forbid_compiles (steady state)
                smetrics.count_compile(self.name, "steady_jit")
                self._warmed.add(sig)
            outs = self.predictor.run(padded)
            outs_per_chunk.append(bucketing.slice_outputs(outs, n))
        if len(outs_per_chunk) == 1:
            return outs_per_chunk[0]
        return [np.concatenate([c[i] for c in outs_per_chunk], axis=0)
                for i in range(len(outs_per_chunk[0]))]


class GenerativeModel:
    """What every engine of the decoder-LM family is: one scope over the
    family's weights, one door for every dispatch (``_run``: ``_launch``
    then ``_fetch``) and the table of executables loaded ahead of time
    — and, where the family carries a ``full`` view, the greedy oracle
    the slot engine is held to (``full_forward_generate``: tests,
    chip_smoke.py, ``ModelDrafter``). :class:`SlotGenerativeModel`, the
    engine a server hosts, inherits from it; built alone over
    ``build_decoder_lm_programs(..., modes=("full",))`` it is the oracle
    and serves nothing. (The name is the one the benchmark's tests patch
    ``_run`` on; renaming it is a ``benchmark`` PR's.)"""

    # the view whose start-up fills the scope: every view's draws the
    # same weights, a slot view's zeroes its pools too
    STARTUP = "full"

    def __init__(self, name: str, programs: Dict,
                 policy: Optional[bucketing.BucketPolicy] = None,
                 scope=None, init: bool = True, dist=None):
        import paddle_tpu.fluid as fluid
        from paddle_tpu.core.lowering import CompiledBlock
        from paddle_tpu.observability import memory as obs_memory
        self.name = name
        # optional SPMD serving: a DistributeConfig lowers every view
        # through the one-dispatch mesh path of core/lowering.py — the
        # params and KV pools live sharded over the mesh and each
        # prefill/decode is a single jit call (docs/serving.md "Serving
        # over a mesh"). None (default) keeps single-device serving.
        self.dist = dist
        self.policy = policy or bucketing.BucketPolicy()
        self.scope = scope or fluid.Scope()
        if init:
            fluid.Executor(fluid.TPUPlace()).run(
                programs[self.STARTUP][1], scope=self.scope)
        # HBM observability: register the scope with the census walk
        obs_memory.note_scope(self.scope)
        self._full = None
        if "full" in programs:
            full_main, _, full_feeds, full_fetch = programs["full"]
            self._full = CompiledBlock(
                full_main.desc, 0, sorted(full_feeds), [full_fetch],
                is_test=True, donate=False, dist=dist)
            # the oracle's sequence: the view's own feed, ids [-1, T, 1]
            self._full_len = int(full_feeds["ids"][0][1])
        self._warmed: set = set()   # the executables' keys, once warm
        # the newest dispatch's first output, still on the device, and
        # the dispatches that found it ready: ``_launch``
        self._prev_output = None
        self._m_starved = {
            view: smetrics.DISPATCH_STARVED.labels(model=name, view=view)
            for view in ("decode", "prefill")}
        self._aot: Dict[Tuple, object] = {}
        self._aot_names = _Loaded(self._aot)

    # -- plumbing --------------------------------------------------------
    def _args(self, cb, feeds):
        state = {n: self.scope.find_var(n) for n in cb.sig.state_names}
        consts = {n: self.scope.find_var(n) for n in cb.sig.const_names}
        return state, consts, feeds, np.uint32(0)

    # True only inside SlotGenerativeModel._run_unfetched
    _fetch_later = False

    def _run(self, cb, aot_key, feeds):
        """One dispatch of ``cb`` and the blocking fetch of its first
        output (``_launch`` then ``_fetch``) — the one door every
        dispatch of an engine goes through, so an observer wraps this.
        The slot engine asks for what ``_launch`` returns instead and
        fetches later (``_run_unfetched``)."""
        launched = self._launch(cb, aot_key, feeds)
        return launched if self._fetch_later else self._fetch(*launched)

    def _launch(self, cb, aot_key, feeds) -> Tuple:
        """One asynchronous dispatch of ``cb``: the new state is in the
        scope and the first output still on the device when this
        returns — ``(output, what _fetch needs beside it)``. While
        tracing, the host's time is named ``serving.<kind>.args`` (scope
        lookups) and ``.dispatch`` (until the async call
        returns, and the state write-back), ``<kind>`` being ``prefill``
        or ``decode`` by ``aot_key[0]`` (a verify step is a decode).
        Before the call it asks whether the device has run dry (``_dry``)
        and counts the dispatch that found it so
        (``paddle_serving_dispatch_starved_total{model, view}``; while
        tracing also a zero-length marker ``serving.starved.<kind>``)."""
        from paddle_tpu.observability import memory as obs_memory
        from paddle_tpu.utils import faults
        trace_on = tctx.active()
        t0 = time.perf_counter() if trace_on else 0.0
        view = "prefill" if aot_key[0].startswith("prefill") else "decode"
        if self.dist is None and self._dry():
            self._m_starved[view].inc()
            if trace_on:
                # zero-length: it marks the instant and covers no gap
                tctx.record_span("serving.starved." + view, t0, t0,
                                 ctx=tctx.current(), model=self.name)
        args = self._args(cb, feeds)
        t1 = time.perf_counter() if trace_on else 0.0
        try:
            # chaos site for the serving OOM-forensics path
            faults.inject("serving.dispatch")
            aot = self._aot.get(aot_key)
            # compile events of this thread count under the block's name
            with obs_runtime.dispatching(cb.obs_label, cb._exes.note,
                                         args):
                if aot is not None:
                    try:
                        fetches, new_state = aot(*args)
                    except Exception as e:
                        # an OOM would only repeat on the jit path (and
                        # its forensics belong to the first failure)
                        if obs_memory.is_oom_error(e):
                            raise
                        # backend mis-mapped the deserialized executable
                        # (XLA:CPU under forced device counts does):
                        # degrade to the (warmed) compile path for the
                        # rest of the run — counted and announced, never
                        # silent
                        import warnings
                        warnings.warn(
                            f"AOT executable {aot_key} of model "
                            f"{self.name!r} failed on this backend "
                            f"({type(e).__name__}); falling back to the "
                            f"compile path", stacklevel=2)
                        smetrics.AOT_FALLBACK.labels(
                            model=self.name, cause="backend_error").inc()
                        self._aot.pop(aot_key, None)
                        fetches, new_state = cb.fn(*args)
                else:
                    fetches, new_state = cb.fn(*args)
        except Exception as e:
            if obs_memory.is_oom_error(e):
                obs_memory.oom_dump(cb, self.scope, e, feeds=feeds)
            raise
        for n, v in new_state.items():
            self.scope.set_var(n, v)
        self._prev_output = fetches[0]
        kind = "serving." + view
        if trace_on:
            ctx = tctx.current()
            tctx.record_span(kind + ".args", t0, t1, ctx=ctx)
            tctx.record_span(kind + ".dispatch", t1, time.perf_counter(),
                             ctx=ctx)
        return fetches[0], kind

    def _dry(self) -> bool:
        """Has the device run everything this engine queued? The output
        of the previous dispatch answers (``jax.Array.is_ready()``: no
        wait, no transfer): one in-order stream, so ready means the
        device is idle now and the dispatch about to be made starts late
        by what the host still has to do. False where nothing can answer:
        no dispatch yet, an output without the method, a deleted array
        (asked first: jax 0.9's ``is_ready()`` on one ends the process)."""
        prev = self._prev_output
        ready = getattr(prev, "is_ready", None)
        if ready is None:
            return False
        deleted = getattr(prev, "is_deleted", None)
        if deleted is not None and deleted():
            return False
        return bool(ready())

    def _fetch(self, out, kind) -> np.ndarray:
        """The blocking fetch of a launched dispatch's output (span
        ``serving.<kind>.fetch``: blocked on the device)."""
        trace_on = tctx.active()
        t0 = time.perf_counter() if trace_on else 0.0
        out = np.asarray(out)
        if trace_on:
            tctx.record_span(kind + ".fetch", t0, time.perf_counter(),
                             ctx=tctx.current())
        return out

    # -- the greedy oracle -----------------------------------------------
    def full_forward_generate(self, prompts: Sequence[np.ndarray],
                              max_new: int) -> List[np.ndarray]:
        """The O(T)-per-token reference: a fresh full causal forward for
        every emitted token (requires the "full" program): the greedy
        oracle the tests and chip_smoke.py compare the slot engine
        against, on the exact same weights."""
        if self._full is None:
            raise RuntimeError("no 'full' program was provided")
        n = len(prompts)
        lens = np.array([len(p) for p in prompts], np.int64)
        bucket = self.policy.bucket_for(n)
        seq = np.zeros((bucket, self._full_len), np.int64)
        for i, p in enumerate(prompts):
            seq[i, :len(p)] = np.asarray(p, np.int64)
        blens = _padding.pad_rows(lens[:, None], bucket)[:, 0]
        out = []
        for s in range(int(max_new)):
            f, _ = self._full.fn(*self._args(
                self._full, {"ids": seq[:, :, None]}))
            logits = np.asarray(f[0])
            tok = logits[np.arange(bucket), blens - 1 + s].argmax(-1)
            out.append(tok.astype(np.int64))
            # append each row's token right after its current end
            # (a prompt and its budget fit the view: blens + s < T)
            seq[np.arange(bucket), blens + s] = out[-1]
        toks = np.stack(out, axis=1)
        return [toks[i] for i in range(n)]

    def full_forward_flops(self, bucket: Optional[int] = None):
        if self._full is None:
            return None
        bucket = bucket or self.policy.batch_buckets[0]
        return self._full.analyzed_flops(
            self.scope,
            {"ids": np.zeros((bucket, self._full_len, 1), np.int64)})


class SlotExhaustedError(RuntimeError):
    """No free decode slot — the scheduler must wait for a leave (or
    shed). Typed so the server can distinguish it from engine errors."""


# -- speculative-decoding drafters (ISSUE 19) -----------------------------
#
# A drafter proposes up to K next tokens for one slot from its COMMITTED
# token history (prompt + accepted generations). The verify dispatch then
# scores the whole window at once and the engine keeps the longest prefix
# whose drafts match what the model would have emitted sequentially —
# the accept rule is exact-match against the on-device samples, which is
# LOSSLESS for greedy and for seeded sampling alike (token_sample's
# Gumbel noise is a pure function of (seed, step, vocab index), so the
# sequential stream is a deterministic function of the logits — matching
# it bit-for-bit is the only way a draft survives).

class NgramDrafter:
    """Model-free prompt-lookup drafting: match the last n-gram of the
    slot's committed tokens against earlier positions in the same
    history and propose the tokens that followed the most recent match.
    Host-side and zero extra HBM — the profitable regime is output that
    re-quotes its own context (code, structured text, greedy cycles),
    where acceptance approaches the full window."""

    def __init__(self, max_ngram: int = 3, min_ngram: int = 1):
        self.max_ngram = int(max_ngram)
        self.min_ngram = max(1, int(min_ngram))

    def propose(self, tokens, k: int):
        n_tok = len(tokens)
        if k <= 0 or n_tok < self.min_ngram + 1:
            return []
        toks = list(tokens)
        # the drafter runs on the hot serving path once per slot per
        # verify step — encode the history once and let bytes.rfind do
        # the suffix search at C speed instead of a python scan
        lo, hi = min(toks), max(toks)
        if 0 <= lo and hi < 256:
            enc, width = (lambda t: bytes(t)), 1
        elif 0 <= lo and hi < (1 << 16):
            enc = lambda t: np.asarray(t, np.uint16).tobytes()
            width = 2
        else:
            enc = lambda t: np.asarray(t, np.uint32).tobytes()
            width = 4
        buf = enc(toks)
        # self-extending lookup: when the matched continuation runs out
        # before filling the window (the match sat near the end of the
        # history), re-match against history + drafts-so-far — on
        # repetitive streams this walks the repeating span and fills
        # the full K instead of stalling at the history frontier
        drafts: list = []
        while len(drafts) < k:
            got = self._lookup(buf, toks, width, k - len(drafts))
            if not got:
                break
            drafts.extend(got)
            toks.extend(got)
            buf += enc(got)
        return drafts

    def _lookup(self, buf, toks, width: int, k: int):
        n_tok = len(toks)
        for n in range(min(self.max_ngram, n_tok - 1),
                       self.min_ngram - 1, -1):
            tail = buf[(n_tok - n) * width:]
            # most recent earlier occurrence of the suffix n-gram:
            # restrict the search window so the match ends before the
            # tail itself, and re-search on token misalignment
            j = buf.rfind(tail, 0, (n_tok - 1) * width)
            while j >= 0 and j % width:
                j = buf.rfind(tail, 0, j + len(tail) - 1)
            if j >= 0:
                cont = toks[j // width + n:j // width + n + k]
                if cont:
                    return cont
        return []


class ModelDrafter:
    """The optional small-draft-model arm: greedy continuations from a
    SEPARATE (smaller) decoder-LM sharing the engine family's program-
    view machinery — its ``full`` view is re-dispatched K times per
    proposal. Pass a :class:`GenerativeModel` built over the draft
    weights' ``full`` view. Useful when histories don't self-repeat (NgramDrafter's
    blind spot); the acceptance rule upstream is unchanged, so a bad
    draft model costs only acceptance length, never correctness."""

    def __init__(self, model: "GenerativeModel"):
        if model._full is None:
            raise ValueError("ModelDrafter needs a model with a 'full' "
                             "program view")
        self.model = model

    def propose(self, tokens, k: int):
        m = self.model
        t_total = m._full_len
        # greedy continuation needs room for k drafts after the context
        ctx = list(tokens)[-(t_total - k):] if k < t_total else []
        if k <= 0 or not ctx:
            return []
        seq = np.zeros((1, t_total), np.int64)
        seq[0, :len(ctx)] = ctx
        drafts = []
        for i in range(k):
            f, _ = m._full.fn(*m._args(
                m._full, {"ids": seq[:, :, None]}))
            tok = int(np.asarray(f[0])[0, len(ctx) - 1 + i].argmax(-1))
            drafts.append(tok)
            seq[0, len(ctx) + i] = tok
        return drafts


def _merge_tokens(feeds):
    """Inside the decode executable: ``tok`` is the host's value where
    ``tok_use_host`` says so and the previous dispatch's output
    ``tok_prev`` [n_slots, 1] elsewhere."""
    import jax.numpy as jnp
    feeds = dict(feeds)
    prev, use_host = feeds.pop("tok_prev"), feeds.pop("tok_use_host")
    tok = feeds["tok"]
    feeds["tok"] = jnp.where(use_host[:, None, None], tok,
                             prev[:, :, None].astype(tok.dtype))
    return feeds


class _Flight(NamedTuple):
    """A decode step that is dispatched and not yet committed."""
    out: object            # its sampled tokens [n_slots, 1], on the device
    kind: str              # what ``_fetch`` needs beside them
    slots: np.ndarray      # the slots it ran, ascending
    ran: np.ndarray        # the same as a mask over all slots
    epoch: np.ndarray      # every slot's admission count at dispatch
    last: np.ndarray       # per ran slot: its budget's last token


class SlotGenerativeModel(GenerativeModel):
    """In-flight batched decoding over a persistent decode-slot pool
    (ISSUE 9) whose KV cache is PAGED (ISSUE 17): the decode executable
    is ONE fixed-shape ``[n_slots]``-row program where each slot carries
    per-row position/active geometry and per-request sampling state, and
    reads its K/V through a ``[n_slots, max_pages]`` page-table feed
    into one shared ``[n_pages, page_size, H*D]`` pool per layer
    (row-major at rest on the chip: ops/kv_attention.py:_paged_pools),
    so HBM holds pages for the requests actually in flight instead of
    ``n_slots`` worst-case rows. Requests JOIN a free slot mid-flight
    (``admit`` prefills the prompt at the nearest prompt bucket and
    scatters its cache rows into the slot's pages via
    ``kv_attention_prefill_paged``) and LEAVE on EOS/max-tokens
    (``step`` reports the leave and frees the slot) — no batch barrier,
    zero steady-state compiles (the page table is a fixed-shape feed:
    join/leave churn re-dispatches, never re-lowers).

    Admission acquires ``ceil((prompt_bucket + budget) / page_size)``
    pages from :class:`~paddle_tpu.serving.kv_pool.PagePool`; full pages
    of the TRUE prompt are shared with earlier requests carrying the
    same token prefix (radix tree, refcounted — prefill skips recomputed
    writes into shared pages via sentinel row ids, the copy-on-write
    boundary page is always private). ``FLAGS_kv_cache_codec`` may
    store the pool as bf16 or int8+per-(position, head) scale planes;
    the dequantizing gather lives in ``ops/pallas/paged_attention.py``.

    Sampling runs ON DEVICE (``token_sample``): greedy when
    ``temperature <= 0`` or ``top_k == 1`` (bit-matches the greedy
    oracle), otherwise temperature/top-k Gumbel sampling keyed only by
    the per-request seed + token index — a sampled stream replays
    identically across server restarts.

    Built from ``build_decoder_lm_programs(..., modes=slot_modes(),
    n_slots=..., prompt_buckets=..., n_pages=..., page_size=...)``.
    Thread discipline: one dispatcher at a time (the server's scheduler
    thread); ``admit``/``step``/``release`` are not internally locked."""

    # the program keys this engine dispatches; warmup, AOT tags and
    # compile-counter kinds are keyed on them. VERIFY is the OPTIONAL
    # speculative-decoding view (ISSUE 19): when the program family
    # carries it, step() switches from one-token decode to
    # draft→verify→commit over a [n_slots, K+1] window.
    PREFILL = "prefill_paged"
    DECODE = "decode_paged"
    VERIFY = "decode_verify_paged"
    # any slot start-up: params + zero-filled pools. The DECODE view's,
    # and nothing allocated between its pools (``_grouped_counters``)
    STARTUP = DECODE

    def __init__(self, name: str, programs: Dict, scope=None,
                 init: bool = True, dist=None, drafter=None):
        from paddle_tpu.core.lowering import CompiledBlock
        pk, dk = self.PREFILL, self.DECODE
        pre = {}
        for key, val in programs.items():
            if key == pk or key.startswith(pk + "@"):
                pre[int(val[2]["ids"][0][1])] = val
        if not pre or dk not in programs:
            raise ValueError(
                f"programs must contain the {pk!r} and {dk!r} views "
                f"(build_decoder_lm_programs(..., modes=slot_modes(), "
                f"n_slots=...)); got {sorted(programs)}")
        self.prompt_buckets = tuple(sorted(pre))
        self.prompt_len = self.prompt_buckets[-1]
        dec_main, _, dec_feeds, dec_fetch = programs[dk]
        self.n_slots = int(dec_feeds["tok"][0][0])
        ver = programs.get(self.VERIFY)
        # the scope and its start-up; the policy is for the server: the
        # most prompts one request may carry
        super().__init__(name, programs,
                         bucketing.BucketPolicy((self.n_slots,)),
                         scope=scope, init=init, dist=dist)
        # HBM observability: program labels and (the pool exists right
        # after startup) the exact KV-pool bytes gauge
        from paddle_tpu.observability import memory as obs_memory
        for p, (m, _s, _f, _o) in pre.items():
            m.desc._obs_name = f"{name}.{pk}@{p}"
        dec_main.desc._obs_name = f"{name}.{dk}"
        if init:
            obs_memory.kv_pool_bytes(self.scope, name)
        self._cb_prefill = {
            p: CompiledBlock(m.desc, 0, sorted(feeds), [fetch],
                             is_test=True, donate=True, dist=dist)
            for p, (m, _s, feeds, fetch) in pre.items()}
        # without a mesh the decode executable takes its tokens from the
        # previous step's output, still on the device, wherever the host
        # does not know better (``_token_feeds``)
        self._cb_decode = CompiledBlock(
            dec_main.desc, 0, sorted(dec_feeds), [dec_fetch],
            is_test=True, donate=True, dist=dist,
            feed_transform=None if dist is not None else _merge_tokens)
        # the optional verify view: one fixed-shape [n_slots, K+1]
        # window executable — its presence flips step() to speculative
        # draft→verify→commit (ISSUE 19)
        self._cb_verify = None
        self.spec_k = 0
        if ver is not None:
            ver_main, _vs, ver_feeds, ver_fetch = ver
            ver_main.desc._obs_name = f"{name}.{self.VERIFY}"
            self._cb_verify = CompiledBlock(
                ver_main.desc, 0, sorted(ver_feeds), [ver_fetch],
                is_test=True, donate=True, dist=dist)
            self.spec_k = int(ver_feeds["tok"][0][1]) - 1
        self.drafter = drafter if drafter is not None else NgramDrafter()
        # metric children bound once: labels() builds a key and takes a
        # lock per call, and a 48-slot step made ~100 of them
        self._m_prefills = smetrics.PREFILLS.labels(model=name)
        self._m_admissions = smetrics.SLOT_ADMISSIONS.labels(model=name)
        self._m_tokens = smetrics.TOKENS_GENERATED.labels(model=name)
        self._m_decode_steps = smetrics.DECODE_STEPS.labels(model=name)
        self._m_sampling_steps = smetrics.SAMPLING_STEPS.labels(
            model=name)
        self._m_tokens_per_step = smetrics.TOKENS_PER_STEP.labels(
            model=name)
        self._m_occupancy = smetrics.SLOT_OCCUPANCY.labels(model=name)
        if ver is not None:
            self._m_spec_proposed = smetrics.SPEC_PROPOSED.labels(
                model=name)
            self._m_spec_accepted = smetrics.SPEC_ACCEPTED.labels(
                model=name)
        self._discover_pool(dec_main, dec_feeds)
        self._discover_state(dec_main, pre[self.prompt_len][2])
        self._fingerprint = hashlib.sha256(json.dumps(
            [pre[p][0].desc.to_dict() for p in self.prompt_buckets]
            + [dec_main.desc.to_dict()]
            + ([ver[0].desc.to_dict()] if ver is not None else []),
            sort_keys=True, default=str).encode()).hexdigest()
        # host mirror of the per-slot device state
        s = self.n_slots
        self._active = np.zeros(s, bool)
        self._tok = np.zeros(s, np.int64)        # last emitted token
        self._seq = np.zeros(s, np.int64)        # true prompt length
        self._gen0 = np.zeros(s, np.int64)       # prompt bucket (gen start)
        self._gen_count = np.zeros(s, np.int64)  # tokens emitted so far
        self._seed = np.zeros(s, np.int64)
        self._temp = np.zeros(s, np.float32)
        self._topk = np.zeros(s, np.int64)
        self._budget = np.zeros(s, np.int64)
        self._eos: List[Optional[int]] = [None] * s
        # committed-token history per slot (prompt + accepted tokens):
        # what the drafter proposes from — host lists, zero extra HBM
        self._hist: List[List[int]] = [[] for _ in range(s)]
        # decode steps are dispatched and committed apart (``step``):
        # slots whose LAST step, by token budget, is dispatched ride the
        # next dispatch masked and leave when theirs is committed; a
        # slot's admission count tells a step still in flight that the
        # slot was released since (EOS seen a step late, a cancel) and
        # its token is nobody's
        self._closing = np.zeros(s, bool)
        self._epoch = np.zeros(s, np.int64)
        # dispatched and uncommitted, oldest first
        self._flights: collections.deque = collections.deque()
        self._last_out = None   # newest decode dispatch's tokens, on device

    def _discover_pool(self, dec_main, dec_feeds):
        """Size the page pool and the host page-table mirror off the
        decode program's pool vars and page-table feed."""
        # any plane of the pool: K (every family with softmax layers)
        # or, for a family whose attention is latent, its latent plane
        gvars = dec_main.desc.global_block.vars
        pool_vars = [v for n, v in gvars.items()
                     if re.search(r"_page_[kc]_\d+$", n)]
        # the window group's pools (layers that attend the last
        # ``window`` positions alone: another page-id space, a ring of
        # pages a slot behind a table of its own)
        window_vars = [v for n, v in gvars.items()
                       if re.search(r"_page_wk_\d+$", n)]
        if not pool_vars and not window_vars:
            raise ValueError(
                f"model {self.name!r}: decode_paged program has no "
                f"*_page_k_*, *_page_c_* or *_page_wk_* pool vars")
        self.max_pages = int(dec_feeds["page_table"][0][1])
        any_pool = (pool_vars or window_vars)[0]
        self.page_size = int(any_pool.shape[1])
        # a family of window layers alone has no full-group pool on the
        # device: its pages are bookkeeping (every slot at full length)
        self.n_pages = int(pool_vars[0].shape[0]) if pool_vars \
            else self.n_slots * self.max_pages
        self.window = self.window_ring = self.n_window_pages = 0
        self._window_layers = len(window_vars)
        if window_vars:
            self.n_window_pages = int(window_vars[0].shape[0])
            self.window_ring = int(dec_feeds["page_table_w"][0][1])
            self.window = next(
                int(op.attrs["window"])
                for op in dec_main.desc.global_block.ops
                if op.type == "kv_attention_decode_paged"
                and op.attrs.get("window"))
        self.cache_len = self.max_pages * self.page_size
        self.max_new = self.cache_len - self.prompt_len
        if self.n_pages < self.max_pages:
            raise ValueError(
                f"model {self.name!r}: pool of {self.n_pages} pages "
                f"cannot hold one worst-case request ({self.max_pages} "
                f"pages) — admission could never succeed")
        self.pool = kv_pool.PagePool(
            self.n_pages, self.page_size, model=self.name,
            window_pages=self.n_window_pages, window=self.window)
        # row-write sentinel: one past the flat pool -> scatter drops it
        self._row_sentinel = self.n_pages * self.page_size
        # host page-table mirror; n_pages is the TABLE sentinel (gather
        # rows land past the pool and are clamped+masked on device)
        self._table = np.full((self.n_slots, self.max_pages),
                              self.n_pages, np.int64)
        self._pending_rows: Optional[np.ndarray] = None
        # the same for the window group: a slot's ring of pages
        self._table_w = np.full((self.n_slots, self.window_ring),
                                self.n_window_pages, np.int64)
        self._pending_rows_w: Optional[np.ndarray] = None
        self._m_window_rows = smetrics.KV_WINDOW_ROWS_ATTENDED.labels(
            model=self.name) if window_vars else None
        # the full group's softmax layers (a latent plane is not one):
        # what their decode steps attend and what they gather
        self._full_layers = sum(
            1 for n in gvars if re.search(r"_page_k_\d+$", n))
        self._in_place_blocks = self._find_in_place_blocks(dec_main)
        self._m_full_rows = {
            what: family.labels(model=self.name) for what, family in (
                ("attended", smetrics.KV_FULL_ROWS_ATTENDED),
                ("gathered", smetrics.KV_FULL_ROWS_GATHERED))}
        # bytes a position costs in each group: every plane of the
        # group's layers (K, V, their scales, a latent layer's two) by
        # its OWN row width (K and V rows, and one group's rows and
        # another's, need not be as wide)
        self.row_bytes = {}
        for group, mark in (("full", "_page_"), ("window", "_page_w")):
            planes = [v for n, v in gvars.items() if re.search(
                mark + r"(k|v|ks|vs|c|i)_\d+$", n)]
            if planes:
                self.row_bytes[group] = sum(
                    int(v.shape[2]) * (4 if v.dtype == "float32" else
                                       1 if v.dtype == "int8" else 2)
                    for v in planes)
                smetrics.KV_ROW_BYTES.labels(
                    model=self.name, group=group).set(self.row_bytes[group])

    def _find_in_place_blocks(self, dec_main) -> Dict[int, int]:
        """{pages a block: layers} of the full group's softmax layers
        that attend their pages in place in a decode step: the op's own
        selection (``ops/kv_attention.py:in_place_block_pages``) asked
        of each decode op's attrs and pool variables, so the rows a step
        READS are counted by the rule — and in the blocks — the lowering
        went by."""
        import jax
        from paddle_tpu.ops import kv_attention
        mesh = self.dist.mesh if self.dist is not None else None
        gvars = dec_main.desc.global_block.vars

        def flat(op, slot):
            var = gvars[op.input(slot)[0]]
            return jax.ShapeDtypeStruct(
                (int(var.shape[0]) * int(var.shape[1]), int(var.shape[2])),
                jax.numpy.dtype(var.dtype))
        blocks = collections.Counter(
            kv_attention.in_place_block_pages(
                op.attrs, flat(op, "PageK"), flat(op, "PageV"),
                flat(op, "PageKS") if op.input("PageKS") else None,
                self.page_size, self.max_pages, mesh)
            for op in dec_main.desc.global_block.ops
            if op.type == "kv_attention_decode_paged")
        blocks.pop(0, None)                 # the layers that copy
        return dict(blocks)

    def _discover_state(self, dec_main, pre_feeds):
        """The second kind of per-slot state (docs/serving.md "Recurrent
        state"): the variables a hybrid family's mixers DECLARE as
        per-slot state (``register_op(..., slot_state=(kind, slots))``:
        a ``kda`` or ``gdn`` layer's delta-rule state and conv window,
        an ``ssd`` or ``s6`` layer's state-space state and conv window,
        a ``shortconv`` layer's window alone), [n_slots, ...] each,
        fixed-size per slot — so admission stays by pages and free
        slots. The prefill view writes the slot its ``state_slot`` feed
        names, the decode view updates every active slot in place.
        ``*_moe_counts_*`` are the expert layers' device-side counters
        (``expert_token_counts``)."""
        from paddle_tpu.core.registry import slot_state_vars
        gvars = dec_main.desc.global_block.vars
        self.state_kinds = {
            kind: sorted(n for names in slots.values() for n in names)
            for kind, slots in slot_state_vars(
                dec_main.desc.global_block).items()}
        self.state_vars = sorted(
            n for names in self.state_kinds.values() for n in names)
        self._count_vars = sorted(n for n in gvars if "_moe_counts_" in n)
        self._counts_seen = (0, 0)        # (totals, device values) read
        self._decode_steps_done = 0
        # (decode steps counted, a copy of each counter): start-up's
        # zeros until the dispatcher takes the first snapshot
        self._counts_snapshot: Tuple = (0, [
            np.zeros(gvars[n].shape, np.int32) for n in self._count_vars])
        # the prefill views' own counters: the rows the expert layers'
        # grouped way held (``*_moe_grouped_*``, in the views of the
        # buckets that take that way: layers the decode view counts
        # too, so they ride its snapshots; made by
        # ``_grouped_counters``), beside the rows it was given — bucket
        # tokens x top_k a layer, known here — per prefill
        self._grouped_vars: List[str] = []
        self._grouped_given: Dict[int, int] = {}    # by prompt bucket
        for p_len, cb in self._cb_prefill.items():
            ops = [op for op in cb._program_desc.global_block.ops
                   if op.type == "expert_ffn_held" and op.inputs.get(
                       "Counts")]
            self._grouped_vars += [op.inputs["Counts"][0] for op in ops]
            self._grouped_given[p_len] = sum(
                p_len * int(op.attrs["top_k"]) for op in ops)
        self._grouped_vars = sorted(set(self._grouped_vars))
        self._grouped_given_done = 0
        self._grouped_seen = (0, 0)   # (given, device values) brought
        # (rows given, a copy of each counter) as of the same instant
        self._grouped_snapshot: Tuple = (0, [])
        self._m_grouped = {
            rows: smetrics.MOE_GROUPED_ROWS.labels(model=self.name,
                                                   rows=rows)
            for rows in ("given", "held")} if self._grouped_vars else {}
        if bool(self.state_vars) != ("state_slot" in pre_feeds):
            raise ValueError(
                f"model {self.name!r}: the decode view carries recurrent "
                f"state {self.state_vars} and the prefill view "
                f"{'a' if 'state_slot' in pre_feeds else 'no'} "
                f"state_slot feed — the views are not one family")

        def nbytes(names):
            return sum(int(np.prod(gvars[n].shape))
                       * (4 if gvars[n].dtype == "float32" else 2)
                       for n in names)

        for kind, names in self.state_kinds.items():
            smetrics.RECURRENT_STATE_BYTES.labels(
                model=self.name, kind=kind).set(nbytes(names))
        # the third kind (docs/serving.md "Latent pages and the indexer's
        # cache"): a latent-attention layer's two planes, paged like K
        # and V and leased with the slot's pages through the one table
        smetrics.LATENT_CACHE_BYTES.labels(model=self.name).set(
            nbytes(n for n in gvars if "_page_c_" in n))
        smetrics.INDEX_CACHE_BYTES.labels(model=self.name).set(
            nbytes(n for n in gvars if "_page_i_" in n))
        # per step the indexer scores every live row of every slot and
        # keeps at most index_topk of them, in each such layer: counted
        # on the host from the slots' own lengths (``_launch_step``)
        mla_ops = [op for op in dec_main.desc.global_block.ops
                   if op.type == "mla_decode_paged"]
        self._dsa_layers = len(mla_ops)
        self._dsa_topk = int(mla_ops[0].attrs["index_topk"]) \
            if mla_ops else 0
        self._m_dsa_scored = smetrics.DSA_ROWS_SCORED.labels(
            model=self.name)
        self._m_dsa_selected = smetrics.DSA_ROWS_SELECTED.labels(
            model=self.name)
        # a state-space layer's prefill scans whole chunks up to the
        # prompt's true length: counted on the host at admission, from
        # the length and the chunk the op was built with
        ssd_ops = [op for op in dec_main.desc.global_block.ops
                   if op.type == "ssd_decode"]
        self._ssd_layers = len(ssd_ops)
        # the chunk a bucket's prefill view scans by, by (op type, bucket)
        self._scan_chunks: Dict[Tuple[str, int], int] = {}
        self._m_ssd_tokens = smetrics.SSD_TOKENS_SCANNED.labels(
            model=self.name)
        self._m_ssd_rows = smetrics.SSD_CHUNK_ROWS.labels(model=self.name)
        # a Mamba-1 (S6) layer's prefill walks whole chunks too: counted
        # as the SSD layers' are
        self._s6_layers = sum(
            op.type == "s6_decode"
            for op in dec_main.desc.global_block.ops)
        self._m_s6_tokens = smetrics.S6_TOKENS_SCANNED.labels(
            model=self.name)
        self._m_s6_rows = smetrics.S6_CHUNK_ROWS.labels(model=self.name)
        # a Gated DeltaNet layer's prefill scans whole blocks of chunks
        # up to the prompt's true length: counted as the SSD layers' are
        self._gdn_layers = sum(
            op.type == "gdn_decode"
            for op in dec_main.desc.global_block.ops)
        self._gdn_chunks: Dict[int, int] = {}       # by prompt bucket
        self._m_gdn_tokens = smetrics.GDN_TOKENS_SCANNED.labels(
            model=self.name)
        self._m_gdn_rows = smetrics.GDN_CHUNK_ROWS.labels(model=self.name)
        # a gated short convolution convolves a prompt's true tokens and
        # a step's running slots: counted on the host, in every such layer
        self._conv_layers = sum(
            op.type == "shortconv_decode"
            for op in dec_main.desc.global_block.ops)
        self._m_conv_tokens = {
            view: smetrics.SHORTCONV_TOKENS.labels(model=self.name,
                                                   view=view)
            for view in ("prefill", "decode")} if self._conv_layers else {}

    def _prefill_chunk(self, p_len: int, op_type: str) -> int:
        """The ``chunk`` the first ``op_type`` op of the ``p_len``
        prefill view was built with."""
        return int(next(
            op for op in
            self._cb_prefill[p_len]._program_desc.global_block.ops
            if op.type == op_type).attrs["chunk"])

    def _gdn_scan_rows(self, length: int, p_len: int) -> int:
        """Rows the chunked scan of the ``p_len`` prefill view computes
        for a prompt of ``length`` tokens (``ops/gdn.py:scan_rows`` at
        the op's ``chunk``, looked up once a bucket)."""
        from paddle_tpu.ops.gdn import scan_rows
        if p_len not in self._gdn_chunks:
            self._gdn_chunks[p_len] = self._prefill_chunk(p_len,
                                                          "gdn_prefill")
        return scan_rows(length, p_len, self._gdn_chunks[p_len])

    def _scan_rows(self, length: int, p_len: int, op_type: str) -> int:
        """Rows the scan of the ``p_len`` prefill view's ``op_type`` ops
        (``ssd_prefill``, ``s6_prefill``) walks for a prompt of
        ``length`` tokens: the whole chunks the length fills, at the
        op's ``chunk`` (at most the bucket; looked up once a bucket)."""
        key = (op_type, p_len)
        if key not in self._scan_chunks:
            self._scan_chunks[key] = min(
                self._prefill_chunk(p_len, op_type), p_len)
        chunk = self._scan_chunks[key]
        return -(-length // chunk) * chunk

    # decode steps between two snapshots of the expert counters
    COUNT_SNAPSHOT_STEPS = 32

    def _snapshot_counts(self):
        """A device-side copy of the expert layers' counters, taken by
        the dispatcher right after a step's state came back (the
        variables themselves are donated to the next step, so no other
        thread may read them): four tiny asynchronous copies every
        ``COUNT_SNAPSHOT_STEPS`` steps, nothing fetched. The prefill
        views' grouped-rows counters go with them, beside the rows the
        prefills dispatched so far were given."""
        import jax.numpy as jnp
        self._counts_snapshot = (
            self._decode_steps_done,
            [jnp.copy(self.scope.find_var(n)) for n in self._count_vars])
        self._grouped_snapshot = (
            self._grouped_given_done,
            [jnp.copy(self.scope.find_var(n)) for n in self._grouped_vars])

    def expert_token_counts(self, sync: bool = False) -> Dict:
        """``{"steps": decode steps counted, "counts": [expert layers,
        2, n_held] totals}`` of the expert layers' device-side counters
        (row 0: tokens each held expert was given by decode steps; row
        1: decode steps in which it was given any), as of the last
        snapshot — at most ``COUNT_SNAPSHOT_STEPS`` steps old, or taken
        now with ``sync`` (the dispatcher's own thread only) — and
        ``paddle_moe_expert_tokens_total`` brought up to them (and
        ``paddle_moe_grouped_rows_total``, the prefills' own). This is
        what fetches from the device: a scrape or a window's edge calls
        it, from any thread; the step's path never does."""
        if sync:
            self._snapshot_counts()
        self._count_grouped_rows()
        steps, arrays = self._counts_snapshot
        if not self._count_vars:
            return {"steps": steps, "counts": None}
        now = np.stack([np.asarray(a) for a in arrays]).astype(np.int64)
        # the device counts in int32 and wraps; totals are kept here
        total, last = self._counts_seen
        delta = (now - last) % (1 << 32)
        self._counts_seen = (total + delta, now)
        for (layer, expert), d in np.ndenumerate(delta[:, 0]):
            if d:
                smetrics.MOE_EXPERT_TOKENS.labels(
                    model=self.name,
                    layer=self._count_vars[layer].rsplit("_", 1)[1],
                    expert=str(expert)).inc(int(d))
        return {"steps": steps, "counts": total + delta}

    def _grouped_counters(self):
        """The prefill views' grouped-rows counters, zeroed, placed as
        the decode view's counter of the same layer is. Made HERE,
        before the first prefill runs, and not by the startup: the
        engine runs the decode view's, and a small buffer allocated
        among its pools moves every pool behind it — Trinity's full
        layer's page gather read 0.74 ms a step slower for four
        counters of 1 KB filled between its pools (PERF.md, PR 44)."""
        import jax
        for name in self._grouped_vars:
            if self.scope.find_var(name) is None:
                like = self.scope.find_var(
                    name.replace("_moe_grouped_", "_moe_counts_"))
                self.scope.set_var(name, jax.device_put(
                    np.zeros(like.shape, like.dtype), like.sharding))

    def _count_grouped_rows(self):
        """``paddle_moe_grouped_rows_total`` brought up to the last
        snapshot: the rows the prefills' grouped way was given (counted
        here, per dispatch) and held (counted on the device), both as
        of the instant the dispatcher took the snapshot."""
        given, arrays = self._grouped_snapshot
        if not arrays:
            return
        now = np.stack([np.asarray(a)[0] for a in arrays]).astype(np.int64)
        brought, last = self._grouped_seen
        # the device counts in int32 and wraps
        self._m_grouped["held"].inc(int(((now - last) % (1 << 32)).sum()))
        self._m_grouped["given"].inc(given - brought)
        self._grouped_seen = (given, now)

    def prompt_bucket_for(self, length: int) -> int:
        """Smallest prompt bucket >= length (the prompt-ladder analogue
        of BucketPolicy.bucket_for)."""
        for p in self.prompt_buckets:
            if length <= p:
                return p
        raise PromptTooLongError(
            f"prompt of length {length} exceeds the prompt bucket "
            f"{self.prompt_len}")

    def free_count(self) -> int:
        return int((~self._active).sum())

    def active_count(self) -> int:
        return int(self._active.sum())

    def occupancy(self) -> float:
        return self.active_count() / float(self.n_slots)

    def free_pages(self) -> int:
        return self.pool.free_count()

    def _decode_feeds(self):
        # a family with rotary positions is fed each token's TRUE
        # position beside its row (generated rows start at the bucket)
        extra = {"position": (self._seq + self._gen_count - 1)[:, None]
                 } if self._dsa_layers else {}
        if self.window:
            self._recycle_window_pages()
            extra["page_table_w"] = self._table_w.copy()
        return {**self._token_feeds(), **extra,
                "pos": (self._gen0 + self._gen_count - 1)[:, None],
                "seq_len": self._seq[:, None],
                "gen_start": self._gen0[:, None],
                "active": (self._active & ~self._closing
                           ).astype(np.int64)[:, None],
                "seed": self._seed[:, None],
                "sample_step": self._gen_count[:, None],
                "temperature": self._temp[:, None],
                "top_k": self._topk[:, None],
                "page_table": self._table.copy()}

    def _recycle_window_pages(self):
        """Before a decode step's feeds: every slot whose token is the
        first of a page of TRUE positions returns the window group's
        pages that now lie behind its window and takes the page it is
        about to write (``PagePool.window_advance``; idempotent, so a
        reader of the next step's feeds may come first). Span
        ``serving.decode.recycle``, inside ``serving.decode.feeds``."""
        true_pos = self._seq + self._gen_count - 1
        enters = np.flatnonzero(self._active & ~self._closing
                                & (true_pos % self.page_size == 0))
        if not enters.size:
            return
        trace_on = tctx.active()
        t0 = time.perf_counter() if trace_on else 0.0
        for slot in enters.tolist():
            if self.pool.window_advance(slot, int(true_pos[slot])):
                self._window_table_row(slot)
        if trace_on:
            tctx.record_span("serving.decode.recycle", t0,
                             time.perf_counter())

    def _window_table_row(self, slot: int):
        ring = np.asarray(self.pool.window_lease(slot).ring, np.int64)
        self._table_w[slot] = np.where(ring >= 0, ring,
                                       self.n_window_pages)

    def _verify_feeds(self, tok_w=None, win_len=None):
        """The verify dispatch's fixed-shape feeds. The sampling feeds
        are per WINDOW POSITION: sample_step[b, i] = gen_count[b] + i,
        so position i consumes exactly the (seed, step) noise draw the
        sequential engine would at that emission — one draw per
        COMMITTED token, rejected positions' draws re-derive identically
        next dispatch (counter-based: no mutable stream state), which is
        what makes seeded replay restart-reproducible."""
        s, k1 = self.n_slots, self.spec_k + 1
        if tok_w is None:
            tok_w = np.zeros((s, k1, 1), np.int64)
            tok_w[:, 0, 0] = self._tok
        if win_len is None:
            win_len = np.ones((s, 1), np.int64)
        steps = self._gen_count[:, None] + np.arange(k1, dtype=np.int64)
        return {"tok": tok_w,
                "pos": (self._gen0 + self._gen_count - 1)[:, None],
                "seq_len": self._seq[:, None],
                "gen_start": self._gen0[:, None],
                "active": self._active.astype(np.int64)[:, None],
                "win_len": win_len,
                "seed": np.tile(self._seed[:, None], (1, k1)),
                "sample_step": steps,
                "temperature": np.tile(self._temp[:, None], (1, k1)),
                "top_k": np.tile(self._topk[:, None], (1, k1)),
                "page_table": self._table.copy()}

    def _prefill_feeds(self, p_len: int):
        # the warm-up's feeds: every page row and the state slot are
        # sentinels, so the dispatch compiles the shapes and writes
        # nothing — every slot's state stays as start-up left it
        return {"ids": np.zeros((1, p_len, 1), np.int64),
                **self._admit_feeds(self.n_slots, p_len),
                "seq_len": np.ones((1, 1), np.int64),
                "seed": np.zeros((1, 1), np.int64),
                "temperature": np.zeros((1, 1), np.float32),
                "top_k": np.zeros((1, 1), np.int64)}

    def _admit_feeds(self, slot: int, p_len: int, trace_on: bool = False):
        """Prefill feed: the flat pool row for each prompt position —
        or the drop sentinel for positions whose pages are SHARED with
        the radix tree (their K/V is already resident and bit-identical
        by construction; rewriting would race other readers only in
        spirit, but skipping also keeps the write volume proportional
        to the non-shared suffix). Warmup (no reservation pending)
        feeds all sentinels: compile the shapes, write nothing."""
        rows = self._pending_rows
        self._pending_rows = None
        if rows is None:
            rows = np.full((p_len, 1), self._row_sentinel, np.int64)
        if self.window:
            rows_w = self._pending_rows_w
            self._pending_rows_w = None
            if rows_w is None:
                rows_w = np.full(
                    (p_len, 1), self.n_window_pages * self.page_size,
                    np.int64)
            return {"page_rows": rows, "page_rows_w": rows_w}
        if not self.state_vars:
            return {"page_rows": rows}
        # the recurrent state is per SLOT, not per page: the prefill
        # recomputes the whole prompt (sentinel rows skip only the page
        # WRITE), so a prefix-shared admission lands the same state.
        # All the host does for it is name the slot (span
        # ``serving.admit.state``: nothing is leased, copied or scrubbed)
        t0 = time.perf_counter() if trace_on else 0.0
        feeds = {"page_rows": rows,
                 "state_slot": np.asarray([[slot]], np.int64)}
        if trace_on:
            tctx.record_span("serving.admit.state", t0,
                             time.perf_counter(), ctx=tctx.current(),
                             model=self.name, slot=slot,
                             kinds=",".join(self.state_kinds))
        return feeds

    def _reserve_capacity(self, slot, prompt, p_len, budget):
        """Admission-time capacity: lease the request's pages (raises
        SlotExhaustedError when the pool can't cover its span), fill
        the slot's table row and stage the prefill's write rows."""
        # draft_window=0 even under speculation: _step_verify caps each
        # window at remaining-1 drafts, so verify writes never pass row
        # p_len + budget - 1. An engine drafting a FULL window at the
        # max_new boundary would need span_for(..., draft_window=spec_k)
        # here — the off-by-K the span formula's parameter guards.
        span = self.pool.span_for(p_len + budget, draft_window=0)
        try:
            pages, n_shared = self.pool.acquire(
                slot, [int(t) for t in prompt], span,
                total_len=len(prompt) + budget if self.window else None)
        except kv_pool.PagesExhaustedError as e:
            raise SlotExhaustedError(
                f"model {self.name!r}: page pool cannot cover a "
                f"{span}-page admission (free_pages="
                f"{self.pool.free_count()}, evictable_cached="
                f"{self.pool.cached_count()}, pages_total="
                f"{self.n_pages}, free_window_pages="
                f"{self.pool.window_free_count()}, free_slots="
                f"{self.free_count()}, "
                f"active_slots={self.active_count()})") from e
        ps = self.page_size
        idx = np.arange(p_len)
        rows = np.asarray(pages, np.int64)[idx // ps] * ps + idx % ps
        rows[idx < n_shared * ps] = self._row_sentinel
        self._pending_rows = rows[:, None]
        self._table[slot, :] = self.n_pages
        self._table[slot, :span] = pages
        if self.window:
            # the window group holds the prompt's LAST pages alone: rows
            # are by true position (a prompt's rows are its positions),
            # and whatever lies before the lease's first page, or in the
            # bucket's padding, is written nowhere
            self._window_table_row(slot)
            lease = self.pool.window_lease(slot)
            held = (idx // ps >= lease.lo) & (idx < len(prompt))
            rows_w = self._table_w[slot][(idx // ps) % self.window_ring] \
                * ps + idx % ps
            rows_w[~held] = self.n_window_pages * ps
            self._pending_rows_w = rows_w[:, None]

    def _release_capacity(self, slot):
        """A prefill dispatch died after acquire: abort the lease (the
        pages it inserted into the prefix tree were never written, so
        they must not survive as cache), scrub the slot's table row,
        and drop any not-yet-consumed write rows so the next unrelated
        admission can't inherit them."""
        self.pool.abort(slot)
        self._table[slot, :] = self.n_pages
        self._table_w[slot, :] = self.n_window_pages
        self._pending_rows = self._pending_rows_w = None

    # -- warmup / AOT ----------------------------------------------------
    def warmup(self, aot_dir: Optional[str] = None,
               persist: bool = True) -> Dict[str, int]:
        """Compile-or-load one prefill executable per prompt bucket plus
        THE decode-slot executable — after this, any join/leave mix of
        in-flight requests dispatches with zero compiles."""
        loaded = compiled = 0
        if aot_dir:
            loaded += self.load_compiled(aot_dir)
        self._grouped_counters()
        pk, dk = self.PREFILL, self.DECODE
        for p in self.prompt_buckets:
            if (pk, p) in self._warmed:
                continue
            smetrics.count_compile(self.name, pk)
            compiled += 1
            self._run(self._cb_prefill[p], (pk, p),
                      self._prefill_feeds(p))
            self._grouped_given_done += self._grouped_given[p]
            self._warmed.add((pk, p))
            if aot_dir and persist:
                self._persist_one(aot_dir, pk, p)
        fresh = (dk,) not in self._warmed
        if fresh:
            smetrics.count_compile(self.name, dk)
            compiled += 1
        # dispatched even when loaded, and twice: the second is fed the
        # first one's tokens from the device, as every step after it is
        # (``_token_feeds``)
        for _ in range(2):
            self._fetch(*self._dispatch_decode(self._decode_feeds()))
        if fresh:
            self._warmed.add((dk,))
            if aot_dir and persist:
                self._persist_one(aot_dir, dk)
        vk = self.VERIFY
        if self._cb_verify is not None and (vk,) not in self._warmed:
            smetrics.count_compile(self.name, vk)
            compiled += 1
            self._run(self._cb_verify, (vk,), self._verify_feeds())
            self._warmed.add((vk,))
            if aot_dir and persist:
                self._persist_one(aot_dir, vk)
        if self._count_vars:
            self._snapshot_counts()        # the copy's own compile, now
        # warmup dispatches touched slot 0's cache rows; no request was
        # live, so just make sure the host mirror says so
        self.reset()
        return {"loaded": loaded, "compiled": compiled}

    def _aot_path(self, dirname: str, kind: str,
                  p_len: Optional[int] = None) -> str:
        tag = kind + (f"_p{p_len}" if p_len else "")
        return os.path.join(
            dirname,
            f"__paged_{tag}_s{self.n_slots}_pg{self.n_pages}"
            f"x{self.page_size}.{self._fingerprint[:12]}.pax")

    def _persist_one(self, dirname: str, kind: str,
                     p_len: Optional[int] = None):
        if kind == self.PREFILL:
            cb, feeds = self._cb_prefill[p_len], self._prefill_feeds(p_len)
        elif kind == self.VERIFY:
            cb, feeds = self._cb_verify, self._verify_feeds()
        else:
            cb, feeds = self._cb_decode, self._decode_feeds()
        lowered = cb.fn.lower(*self._args(cb, feeds))
        save_executable(self._aot_path(dirname, kind, p_len), lowered)

    def load_compiled(self, dirname: str) -> int:
        """Load every persisted executable matching this program
        fingerprint; returns how many now serve without a compile. The
        fingerprint hashes the program descs VERBATIM — including
        generated intermediate var names, which restart identically in a
        fresh process (the server-restart scenario this serves) but
        shift if the programs are REbuilt inside one process; a mismatch
        is safe, it just recompiles."""
        # the devices the executables were compiled for: the mesh's, or
        # the one the start-up put the scope's arrays on
        mesh = self.dist.mesh if self.dist is not None else None
        devices = mesh.devices.flat if mesh is not None else \
            self.scope.find_var(
                self._cb_decode.sig.state_names[0]).devices()
        keys = [(self.PREFILL, p) for p in self.prompt_buckets] \
            + [(self.DECODE,)] \
            + ([(self.VERIFY,)] if self._cb_verify is not None else [])
        n = 0
        for key in keys:
            exe = load_executable(self._aot_path(dirname, *key), devices)
            if exe is not None:
                self._aot[key] = exe
                self._warmed.add(key)
                n += 1
        return n

    # -- slot lifecycle --------------------------------------------------
    def admit(self, prompt, *, seed: int = 0, temperature: float = 0.0,
              top_k: int = 0, max_new: Optional[int] = None,
              eos_id: Optional[int] = None
              ) -> Tuple[int, int, Optional[str]]:
        """JOIN: prefill ``prompt`` into a free slot (nearest prompt
        bucket) and sample its first token on-device. Returns
        (slot, first_token, done_cause); done_cause is None while the
        request stays in flight, or 'eos'/'max_new' when the very first
        token already finished it (the slot is then freed again)."""
        prompt = np.asarray(prompt, np.int64).reshape(-1)
        length = len(prompt)
        if length < 1:
            raise ValueError("empty prompt")
        if length > self.prompt_len:
            raise PromptTooLongError(
                f"prompt of length {length} exceeds the prompt bucket "
                f"{self.prompt_len}")
        free = np.flatnonzero(~self._active)
        if free.size == 0:
            raise SlotExhaustedError(
                f"model {self.name!r}: all {self.n_slots} decode slots "
                f"are in flight (free_slots=0, "
                f"active_slots={self.n_slots})")
        slot = int(free[0])
        p_len = self.prompt_bucket_for(length)
        budget = self.max_new if max_new is None else int(max_new)
        # capacity is set by the PROMPT BUCKET, not the true length:
        # generated KV rows land from gen_start = p_len (the last fed-
        # back token writes at p_len + budget - 2, which must stay
        # inside the cache — otherwise the write silently misses and
        # late tokens lose their predecessor's keys)
        if budget < 1 or budget > self.cache_len - p_len:
            raise ValueError(
                f"max_new {budget} outside the cache budget "
                f"(1..{self.cache_len - p_len} for a prompt padded to "
                f"bucket {p_len})")
        # one tracing check per admission; timestamps only when on
        trace_on = tctx.active()
        t0 = time.perf_counter() if trace_on else 0.0
        self._reserve_capacity(slot, prompt, p_len, budget)
        if trace_on:
            tctx.record_span("serving.admit.reserve", t0,
                             time.perf_counter(), ctx=tctx.current(),
                             model=self.name, slot=slot)
        key = (self.PREFILL, p_len)
        if key not in self._warmed:
            smetrics.count_compile(self.name, f"steady_{self.PREFILL}")
            self._warmed.add(key)
        # span named by the PROMPT BUCKET the admission landed on, under
        # the admitting request's trace (the scheduler activates it)
        try:
            with tctx.span(f"serving.prefill@{p_len}", model=self.name,
                           slot=slot) as pctx:
                t0 = time.perf_counter() if trace_on else 0.0
                ids = np.zeros((1, p_len, 1), np.int64)
                ids[0, :length, 0] = prompt
                feeds = {
                    "ids": ids,
                    **self._admit_feeds(slot, p_len, trace_on),
                    "seq_len": np.asarray([[length]], np.int64),
                    "seed": np.asarray([[int(seed)]], np.int64),
                    "temperature": np.asarray([[float(temperature)]],
                                              np.float32),
                    "top_k": np.asarray([[int(top_k)]], np.int64)}
                if trace_on:
                    tctx.record_span("serving.prefill.feeds", t0,
                                     time.perf_counter(), ctx=pctx)
                launched = self._run_unfetched(self._cb_prefill[p_len],
                                               key, feeds)
                self._grouped_given_done += self._grouped_given[p_len]
                # the scheduler runs ahead (a step is in flight): queue
                # the step after it behind the prefill BEFORE waiting
                # for the first token, so the device goes from the
                # prefill into a decode step and not into the host's
                # turn-around; this slot joins the step after that one
                if 0 < len(self._flights) < 3:
                    self._launch_step()
                tok = self._fetch(*launched)
        except BaseException:
            self._release_capacity(slot)
            raise
        self._m_prefills.inc()
        self._m_admissions.inc()
        self._m_tokens.inc()
        if self._ssd_layers:
            self._m_ssd_tokens.inc(length * self._ssd_layers)
            self._m_ssd_rows.inc(self._scan_rows(length, p_len,
                                                 "ssd_prefill")
                                 * self._ssd_layers)
        if self._s6_layers:
            self._m_s6_tokens.inc(length * self._s6_layers)
            self._m_s6_rows.inc(self._scan_rows(length, p_len, "s6_prefill")
                                * self._s6_layers)
        if self._gdn_layers:
            self._m_gdn_tokens.inc(length * self._gdn_layers)
            self._m_gdn_rows.inc(self._gdn_scan_rows(length, p_len)
                                 * self._gdn_layers)
        if self._conv_layers:
            self._m_conv_tokens["prefill"].inc(length * self._conv_layers)
        first = int(np.asarray(tok).reshape(-1)[0])
        self._active[slot] = True
        self._tok[slot] = first
        self._seq[slot] = length
        self._gen0[slot] = p_len
        self._gen_count[slot] = 1
        self._hist[slot] = [int(t) for t in prompt] + [first]
        self._seed[slot] = int(seed)
        self._temp[slot] = float(temperature)
        self._topk[slot] = int(top_k)
        self._budget[slot] = budget
        self._eos[slot] = eos_id
        done = None
        if eos_id is not None and first == eos_id:
            done = "eos"
        elif budget <= 1:
            done = "max_new"
        if done:
            self.release(slot, cause=done)
        else:
            self._m_occupancy.set(self.occupancy())
        return slot, first, done

    def step(self, ahead: bool = False
             ) -> List[Tuple[int, int, Optional[str]]]:
        """One dispatch over the WHOLE pool (free slots ride along
        masked). Returns (slot, token, done_cause) events in commit
        order; slots that hit EOS or their token budget are released —
        the LEAVE side of in-flight batching.

        Without a verify view this is one decode dispatch = one token
        per active slot. With one (ISSUE 19) it is draft→verify→commit:
        the drafter proposes up to K tokens per slot, ONE fixed-shape
        verify dispatch scores every slot's window, and each slot
        commits its accepted prefix plus the bonus token — up to K+1
        events per slot per step, bit-identical to what the sequential
        path would have emitted (exact-match acceptance against the
        on-device samples).

        ``ahead`` (the server's scheduler passes it) queues the NEXT
        decode step on the device before this one's tokens are fetched:
        its token feed is this step's output, still on the device, so
        the host's work on a step's tokens — this commit, the
        scheduler's, the next dispatch — runs beside a device that is
        never waiting for it. (Keeping six dispatched was tried and
        taken back: 2 % fewer tokens a second on the chip, an
        admission's prefill waiting behind all of them, and no steadier:
        PERF.md, PR 31.) The events are the same; the scope is then a
        dispatch or two ahead of them; a slot that ends on EOS or is
        cancelled has run a step or two more than it needed, whose
        tokens are dropped (its rows and state are the next admission's
        to overwrite: everything later is queued behind it). While a step
        is in flight ``admit`` queues the one after it behind its
        prefill, before it waits for the first token, so the device
        goes from a prefill into a decode step and not into the host's
        turn-around; the admitted slot joins the step after that one. A
        later call without ``ahead`` commits the oldest step in flight
        and dispatches nothing. Ignored with a verify view (drafts need
        the committed history) and under a mesh."""
        if self._cb_verify is not None:
            live = np.flatnonzero(self._active)
            return self._step_verify(live) if live.size else []
        if (self.DECODE,) not in self._warmed:
            smetrics.count_compile(self.name, f"steady_{self.DECODE}")
            self._warmed.add((self.DECODE,))
        if not self._flights and not self._launch_step():
            return []
        if ahead and self.dist is None and len(self._flights) < 2:
            self._launch_step()
        return self._commit_step(self._flights.popleft())

    def _token_feeds(self) -> Dict:
        """The decode step's token feeds. Without a mesh: the host's
        last tokens, the newest dispatch's output (on the device), and
        which to take per slot — the host's for every slot that the
        newest step in flight (dispatched, uncommitted) did not run or
        no longer owns, all of them when nothing is in flight; the
        executable merges them (``_merge_tokens``), so no dispatch is
        spent on it and the step has one signature. Under a mesh only
        the host's: nothing runs ahead there."""
        feeds = {"tok": self._tok[:, None, None]}
        if self.dist is not None:
            return feeds
        if self._last_out is None:
            # before the first dispatch: zeros, placed as an output will
            # be (a committed array in an uncommitted one's place is
            # another signature to ``jit``; warmup dispatches twice, so
            # a real output has taken this one's place either way)
            import jax
            like = self.scope.find_var(self._cb_decode.sig.state_names[0])
            zeros = np.zeros((self.n_slots, 1), np.int32)
            self._last_out = jax.device_put(
                zeros, next(iter(like.devices()))
                if getattr(like, "committed", False) else None)
        feeds["tok_prev"] = self._last_out
        if self._flights:
            after = self._flights[-1]
            feeds["tok_use_host"] = ~after.ran | (self._epoch != after.epoch)
        else:
            feeds["tok_use_host"] = np.ones(self.n_slots, bool)
        return feeds

    def _run_unfetched(self, cb, aot_key, feeds) -> Tuple:
        """A dispatch through ``_run`` whose output stays on the device:
        what ``_fetch`` takes."""
        self._fetch_later = True
        try:
            return self._run(cb, aot_key, feeds)
        finally:
            self._fetch_later = False

    def _dispatch_decode(self, feeds) -> Tuple:
        """The decode step, unfetched; its tokens, still on the device,
        are the next step's token feed."""
        launched = self._run_unfetched(self._cb_decode, (self.DECODE,),
                                       feeds)
        self._last_out = launched[0]
        return launched

    def _count_sampling_step(self):
        """Count the step just dispatched if token_sample ran its
        sampled branch in it: some row fed temperature > 0 with top_k
        != 1. Released slots feed zeros, so that is a LIVE request; the
        host's mirror answers, nothing is read from the device."""
        if ((self._temp > 0.0) & (self._topk != 1)).any():
            self._m_sampling_steps.inc()

    def _launch_step(self) -> bool:
        """Dispatch one decode step, behind those in flight, over the
        slots that still have a token to make (False when there is
        none) and advance their counts; nothing is fetched."""
        ran = self._active & ~self._closing
        if not ran.any():
            return False
        # one tracing check per step; timestamps only when on
        trace_on = tctx.active()
        t0 = time.perf_counter() if trace_on else 0.0
        feeds = self._decode_feeds()
        if trace_on:
            tctx.record_span("serving.decode.feeds", t0,
                             time.perf_counter())
        out, kind = self._dispatch_decode(feeds)
        self._count_sampling_step()
        slots = np.flatnonzero(ran)
        if self._conv_layers:
            self._m_conv_tokens["decode"].inc(
                len(slots) * self._conv_layers)
        # each running slot's live rows, before this step's own
        live = self._seq[slots] + self._gen_count[slots]
        if self._dsa_layers:
            self._m_dsa_scored.inc(int(live.sum()) * self._dsa_layers)
            self._m_dsa_selected.inc(int(np.minimum(
                live, self._dsa_topk).sum()) * self._dsa_layers)
        if self.window:
            # what the window layers attend this step: at most a window's
            self._m_window_rows.inc(int(np.minimum(
                live, self.window).sum()) * self._window_layers)
        if self._full_layers:
            # a full layer attends a running slot's live rows; what it
            # READS for them is every slot's whole table where it
            # gathers, the running slots' live blocks where it attends
            # in place
            self._m_full_rows["attended"].inc(
                int(live.sum()) * self._full_layers)
            read = self.n_slots * self.cache_len * (
                self._full_layers - sum(self._in_place_blocks.values()))
            if self._in_place_blocks:
                # the kernel's own plan, on the host
                from paddle_tpu.ops.pallas.paged_attention import \
                    attend_rows_read
                pos = self._gen0[slots] + self._gen_count[slots] - 1
                read += sum(layers * int(attend_rows_read(
                    self._seq[slots], self._gen0[slots], pos,
                    self.page_size, block).sum())
                    for block, layers in self._in_place_blocks.items())
            self._m_full_rows["gathered"].inc(read)
        self._gen_count[slots] += 1
        last = self._gen_count[slots] >= self._budget[slots]
        self._closing[slots[last]] = True
        self._decode_steps_done += 1
        if self._count_vars and \
                self._decode_steps_done % self.COUNT_SNAPSHOT_STEPS == 0:
            self._snapshot_counts()
        self._flights.append(_Flight(out, kind, slots, ran,
                                     self._epoch.copy(), last))
        return True

    def _commit_step(self, flight: _Flight
                     ) -> List[Tuple[int, int, Optional[str]]]:
        """Fetch a dispatched step's tokens (blocks until the device has
        run it) and commit them: the events, the releases."""
        out = self._fetch(flight.out, flight.kind).reshape(-1)
        trace_on = tctx.active()
        t0 = time.perf_counter() if trace_on else 0.0
        self._m_decode_steps.inc()
        events = []
        for slot, last in zip(flight.slots.tolist(), flight.last.tolist()):
            if self._epoch[slot] != flight.epoch[slot]:
                continue          # released since the dispatch
            tok = int(out[slot])
            self._tok[slot] = tok
            self._hist[slot].append(tok)
            self._m_tokens_per_step.observe(1.0)
            eos = self._eos[slot]
            done = None
            if eos is not None and tok == eos:
                done = "eos"
            elif last:
                done = "max_new"
            if done:
                self.release(slot, cause=done)
            events.append((slot, tok, done))
        self._m_tokens.inc(len(events))
        self._m_occupancy.set(self.occupancy())
        if trace_on:
            tctx.record_span("serving.decode.commit", t0,
                             time.perf_counter())
        return events

    def _step_verify(self, live) -> List[Tuple[int, int, Optional[str]]]:
        """Draft→verify→commit (ISSUE 19). Window semantics: position 0
        carries the slot's last committed token (re-writing its KV row
        with bit-identical values), positions 1..K the drafts; the
        sampled output at position i is the token the sequential engine
        would emit at step gen_count + i GIVEN the window prefix, so
        draft i survives iff it equals sample i-1 — and the commit is
        the accepted prefix plus one bonus token. Greedy output is
        bit-identical to the non-speculative scheduler; temperature>0
        stays lossless because acceptance compares against the exact
        counter-based sample of each (seed, step)."""
        trace_on = tctx.active()
        t0 = time.perf_counter() if trace_on else 0.0
        s, k1 = self.n_slots, self.spec_k + 1
        tok_w = np.zeros((s, k1, 1), np.int64)
        tok_w[:, 0, 0] = self._tok
        win_len = np.ones((s, 1), np.int64)
        drafts: Dict[int, List[int]] = {}
        proposed = 0
        for slot in live:
            slot = int(slot)
            # a window commits at most accepted+1 tokens: never draft
            # past the remaining budget, nor past the cache end (the
            # admission invariant makes the budget cap the binding one)
            remaining = int(self._budget[slot] - self._gen_count[slot])
            pos0 = int(self._gen0[slot] + self._gen_count[slot] - 1)
            kq = min(self.spec_k, remaining - 1, self.cache_len - 1 - pos0)
            d = []
            if kq > 0:
                d = [int(t) for t in
                     self.drafter.propose(self._hist[slot], kq)][:kq]
            drafts[slot] = d
            for i, t in enumerate(d):
                tok_w[slot, 1 + i, 0] = t
            win_len[slot, 0] = 1 + len(d)
            proposed += len(d)
        if (self.VERIFY,) not in self._warmed:
            smetrics.count_compile(self.name, f"steady_{self.VERIFY}")
            self._warmed.add((self.VERIFY,))
        feeds = self._verify_feeds(tok_w, win_len)
        if trace_on:
            # the drafter's proposals are part of building the window
            tctx.record_span("serving.decode.feeds", t0,
                             time.perf_counter())
        out = self._run(self._cb_verify, (self.VERIFY,), feeds)
        self._count_sampling_step()
        t0 = time.perf_counter() if trace_on else 0.0
        out = np.asarray(out).reshape(s, k1)
        self._m_decode_steps.inc()
        self._m_spec_proposed.inc(proposed)
        events = []
        committed_total = accepted_total = 0
        for slot in live:
            slot = int(slot)
            d = drafts[slot]
            t = out[slot]
            a = 0
            while a < len(d) and d[a] == int(t[a]):
                a += 1
            accepted_total += a
            commit = [int(x) for x in t[:a + 1]]
            eos = self._eos[slot]
            done = None
            n_commit = 0
            for tok in commit:
                n_commit += 1
                self._tok[slot] = tok
                self._gen_count[slot] += 1
                self._hist[slot].append(tok)
                if eos is not None and tok == eos:
                    done = "eos"
                elif self._gen_count[slot] >= self._budget[slot]:
                    done = "max_new"
                events.append((slot, tok, done))
                if done:
                    break
            committed_total += n_commit
            self._m_tokens_per_step.observe(float(n_commit))
            if done:
                self.release(slot, cause=done)
        self._m_spec_accepted.inc(accepted_total)
        self._m_tokens.inc(committed_total)
        self._m_occupancy.set(self.occupancy())
        if trace_on:
            tctx.record_span("serving.decode.commit", t0,
                             time.perf_counter())
        return events

    def release(self, slot: int, cause: str = "cancelled"):
        """LEAVE: free ``slot`` and return its page lease for the next
        admission (nothing is scrubbed on the device: the next holder's
        mask admits only rows it wrote itself)."""
        if not self._active[slot]:
            return
        self.pool.release(slot)
        self._table[slot, :] = self.n_pages
        self._table_w[slot, :] = self.n_window_pages
        self._active[slot] = False
        self._closing[slot] = False
        self._epoch[slot] += 1
        self._eos[slot] = None
        # an idle row feeds greedy: token_sample runs its sampled branch
        # where ANY row of the batch samples, and has no Active input
        self._temp[slot] = 0.0
        self._topk[slot] = 0
        smetrics.SLOT_EVICTIONS.labels(model=self.name,
                                       cause=cause).inc()
        self._m_occupancy.set(self.occupancy())

    def reset(self):
        self.pool.reset()
        self._table[:] = self.n_pages
        self._table_w[:] = self.n_window_pages
        self._pending_rows = self._pending_rows_w = None
        self._active[:] = False
        self._closing[:] = False
        self._flights.clear()
        self._gen_count[:] = 0
        self._eos = [None] * self.n_slots
        self._temp[:] = 0.0
        self._topk[:] = 0
        self._m_occupancy.set(0.0)

    # -- convenience: drive the pool to completion -----------------------
    def generate(self, prompts: Sequence, max_new: Optional[int] = None,
                 temperature: float = 0.0, top_k: int = 0,
                 seeds: Optional[Sequence[int]] = None,
                 eos_id: Optional[int] = None) -> List[np.ndarray]:
        """Admit every prompt (queuing past ``n_slots`` until slots
        free) and step the pool until all are done — the single-caller
        convenience the parity tests drive; the server's scheduler does
        the same dance with interleaved arrivals. Assumes exclusive use
        of the pool."""
        pending = list(range(len(prompts)))[::-1]
        collected: Dict[int, list] = {i: [] for i in range(len(prompts))}
        slot2idx: Dict[int, int] = {}
        while pending or slot2idx:
            while pending and self.free_count() > 0:
                i = pending.pop()
                slot, first, done = self.admit(
                    prompts[i],
                    seed=int(seeds[i]) if seeds is not None else 0,
                    temperature=temperature, top_k=top_k,
                    max_new=max_new, eos_id=eos_id)
                collected[i].append(first)
                if not done:
                    slot2idx[slot] = i
            for slot, tok, done in self.step():
                i = slot2idx.get(slot)
                if i is None:
                    continue
                collected[i].append(tok)
                if done:
                    del slot2idx[slot]
        return [np.asarray(collected[i], np.int64)
                for i in range(len(prompts))]


def make_slot_model(name: str, programs: Dict, scope=None,
                    init: bool = True, dist=None,
                    drafter=None) -> SlotGenerativeModel:
    """Build the slot engine over ``programs`` (from
    ``build_decoder_lm_programs(..., modes=transformer.slot_modes())``);
    raises ``ValueError`` naming the missing ``prefill_paged`` /
    ``decode_paged`` views when handed a family without them. ``dist``
    (a ``DistributeConfig``) lowers every view over its mesh — see
    docs/serving.md. ``drafter`` overrides the speculative proposer
    (default :class:`NgramDrafter`) for engines built with a verify
    view."""
    return SlotGenerativeModel(name, programs, scope=scope, init=init,
                               dist=dist, drafter=drafter)
