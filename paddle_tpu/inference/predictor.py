"""Predictor API (reference: inference/api/paddle_api.h PaddlePredictor,
api/api_impl.cc NativePaddlePredictor, api/analysis_predictor.cc
AnalysisPredictor + AnalysisConfig; CreatePaddlePredictor factory)."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

import paddle_tpu.fluid as fluid


@dataclass
class AnalysisConfig:
    """reference: api/paddle_analysis_config.h. GPU/MKLDNN/TensorRT knobs
    are accepted for API parity and ignored (XLA compiles the whole graph;
    there is no subgraph offload tier on TPU)."""

    model_dir: str = ""
    prog_file: str = ""
    params_file: str = ""
    # telemetry tag for this model's serving metrics (the `model` label
    # on paddle_serving_aot_fallback_total etc.); defaults to the model
    # dir's basename
    model_tag: str = ""
    # reference: switch_ir_optim — run the inference transpiler's IR
    # rewrites (BN fold) before compiling
    ir_optim: bool = True
    use_gpu: bool = False          # parity no-op
    device_id: int = 0             # parity no-op
    enable_memory_optim_: bool = True   # parity no-op (XLA buffer reuse)
    tensorrt: dict = field(default_factory=dict)  # parity no-op

    def enable_use_gpu(self, memory_pool_init_size_mb=0, device_id=0):
        self.use_gpu = True
        self.device_id = device_id

    def disable_gpu(self):
        self.use_gpu = False

    def switch_ir_optim(self, x: bool = True):
        self.ir_optim = x

    def enable_memory_optim(self):
        self.enable_memory_optim_ = True

    def enable_tensorrt_engine(self, **kw):
        """reference: analysis_config TensorRT offload — no TPU analogue;
        recorded and ignored (XLA compiles the full graph)."""
        self.tensorrt = kw


class PaddlePredictor:
    """reference: paddle_api.h PaddlePredictor::Run. Each distinct input
    shape signature compiles once and is cached (the reference re-ran the
    interpreter per call; here repeat calls hit the XLA executable cache,
    executor.py program cache capability)."""

    def __init__(self, config: AnalysisConfig):
        self._config = config
        self._scope = fluid.Scope()
        self._exe = fluid.Executor(fluid.TPUPlace())
        # load under a guard so startup-less restore does not pollute the
        # caller's default programs
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            program, feeds, fetches = fluid.io.load_inference_model(
                config.model_dir, self._exe,
                model_filename=config.prog_file or None,
                params_filename=config.params_file or None,
                scope=self._scope)
        if config.ir_optim:
            self._run_analysis_passes(program)
        self._program = program
        self._feed_names = feeds
        self._fetch_names = fetches

    # the Analysis pipeline (reference: analysis_predictor.cc Analyzer +
    # ir_pass_manager — the pass list AnalysisConfig.pass_builder seeds).
    # Scope-dependent folds (conv_bn via the transpiler, affine_channel,
    # embedding_fc_lstm) see the loaded params.
    ANALYSIS_PASSES = [
        "infer_clean_graph_pass",
        "is_test_pass",
        "conv_affine_channel_fuse_pass",
        "conv_bn_fuse_pass",            # delegates to InferenceTranspiler
        "conv_elementwise_add2_act_fuse_pass",
        "conv_elementwise_add_act_fuse_pass",
        "conv_elementwise_add_fuse_pass",
        # rnn/seq fusions BEFORE fc_fuse — their patterns start at the
        # mul+add gate projection that fc_fuse would consume
        "embedding_fc_lstm_fuse_pass",
        "fc_lstm_fuse_pass",
        "fc_gru_fuse_pass",
        "seqconv_eltadd_relu_fuse_pass",
        "seqpool_concat_fuse_pass",
        "seq_concat_fc_fuse_pass",
        "transpose_flatten_concat_fuse_pass",
        "fc_fuse_pass",
    ]

    def _run_analysis_passes(self, program):
        from paddle_tpu.fluid import ir_pass as irp
        block = program.desc.global_block
        for name in self.ANALYSIS_PASSES:
            p = irp.get_pass(name)
            p.scope = self._scope
            p(irp.Graph(block))
        program.desc.bump_version()

    def get_input_names(self) -> List[str]:
        return list(self._feed_names)

    def get_output_names(self) -> List[str]:
        return list(self._fetch_names)

    def run(self, inputs) -> List[np.ndarray]:
        """inputs: dict {feed name: array} or list in feed order."""
        if not isinstance(inputs, dict):
            inputs = dict(zip(self._feed_names, inputs))
        if self._aot:
            if self.has_aot_for(inputs):
                # a backend failure inside counts cause=backend_error
                outs = self._run_aot(inputs)
                if outs is not None:
                    return outs
            else:
                self._count_fallback("shape_miss")
        elif self._aot_load_attempted:
            # load_compiled was called but nothing (usable) loaded —
            # this predictor intended to serve AOT and is now silently
            # compiling at request time; make that visible
            self._count_fallback("no_artifact")
        outs = self._exe.run(self._program, feed=inputs,
                             fetch_list=self._fetch_names,
                             scope=self._scope)
        return [np.asarray(o) for o in outs]

    def _count_fallback(self, cause: str):
        """paddle_serving_aot_fallback_total{model,cause} — the
        AOT-miss-to-JIT counter (ISSUE 8 satellite; declared in
        serving/metrics.py, preregistered in the exporter catalog)."""
        try:
            from paddle_tpu.serving import metrics as smetrics
            smetrics.AOT_FALLBACK.labels(
                model=self._model_tag(), cause=cause).inc()
        except Exception:
            pass      # telemetry must never fail an inference

    def _model_tag(self) -> str:
        import os
        return (self._config.model_tag
                or os.path.basename(
                    os.path.normpath(self._config.model_dir or ""))
                or "default")

    # reference spelling
    __call__ = run

    # -- AOT executable persistence ------------------------------------
    # The reference's model-load path deserializes a ready program and
    # starts serving (analysis_predictor.cc LoadProgramDesc + optimized
    # executor); XLA re-introduces a compile at first inference. These
    # methods close that cold-start gap: the COMPILED XLA executable is
    # serialized next to the StableHLO export — ONE FILE PER FEED-SHAPE
    # SIGNATURE (`__compiled__.<digest>.pax`), so a shape-bucketed
    # server (paddle_tpu/serving) boots its whole bucket ladder from
    # disk without invoking the compiler. The legacy single-file name
    # (`__compiled__.pax`) still loads.

    _aot: dict = None                  # {shape digest: (executable, sig)}
    _aot_load_attempted = False
    AOT_FILENAME = "__compiled__.pax"  # legacy (pre-multi-signature)
    AOT_PREFIX = "__compiled__."
    AOT_SUFFIX = ".pax"

    def _program_fingerprint(self) -> str:
        import hashlib
        import json as _json
        blob = _json.dumps(self._program.desc.to_dict(), sort_keys=True,
                           default=str)
        return hashlib.sha256(blob.encode()).hexdigest()

    @staticmethod
    def _shape_digest(feed_shapes) -> str:
        """Stable 16-hex digest of a {name: (shape, dtype)} signature —
        the per-executable filename key."""
        import hashlib
        blob = repr(sorted((n, tuple(s), str(d))
                           for n, (s, d) in feed_shapes.items()))
        return hashlib.sha256(blob.encode()).hexdigest()[:16]

    def _input_shapes(self, inputs) -> dict:
        return {n: (tuple(np.shape(v)), str(np.asarray(v).dtype))
                for n, v in inputs.items()}

    def _digest_of_inputs(self, inputs) -> str:
        return self._shape_digest(self._input_shapes(
            {n: inputs[n] for n in self._feed_names if n in inputs}))

    def has_aot_for(self, inputs) -> bool:
        """Whether a loaded AOT executable matches these input shapes."""
        if not self._aot:
            return False
        if not isinstance(inputs, dict):
            inputs = dict(zip(self._feed_names, inputs))
        return self._digest_of_inputs(inputs) in self._aot

    def aot_signatures(self) -> List[dict]:
        """The feed-shape signatures currently loaded (one per
        executable)."""
        return [dict(sig["feed_shapes"])
                for _, sig in (self._aot or {}).values()]

    def _aot_args(self, cb_sig, inputs):
        state = {n: self._scope.find_var(n) for n in cb_sig["state_names"]}
        consts = {n: self._scope.find_var(n) for n in cb_sig["const_names"]}
        feeds = {n: np.asarray(inputs[n]) for n in cb_sig["feed_names"]}
        return state, consts, feeds

    def save_compiled(self, dirname: str, example_inputs) -> str:
        """AOT-compile for the example input shapes and persist the
        serialized executable — one file PER feed-shape signature
        (`__compiled__.<digest>.pax`), so calling this once per batch
        bucket gives the serving warmup a full ladder to load from
        disk instead of recompiling (ISSUE 8 satellite; the gap the old
        single-file layout admitted)."""
        import os
        import pickle
        from jax.experimental import serialize_executable as se
        from paddle_tpu.core.lowering import CompiledBlock

        if not isinstance(example_inputs, dict):
            example_inputs = dict(zip(self._feed_names, example_inputs))
        feed_names = sorted(example_inputs)
        # donate=False: a served executable is called repeatedly against
        # the same resident param buffers
        cb = CompiledBlock(self._program.desc, 0, feed_names,
                           self._fetch_names, is_test=True, donate=False)
        sig = {"feed_names": feed_names,
               "fetch_names": list(self._fetch_names),
               "state_names": list(cb.sig.state_names),
               "const_names": list(cb.sig.const_names),
               "program_fingerprint": self._program_fingerprint()}
        state, consts, feeds = self._aot_args(sig, example_inputs)
        lowered = cb.fn.lower(state, consts, feeds, np.uint32(0))
        payload = se.serialize(lowered.compile())
        sig["feed_shapes"] = {n: (tuple(a.shape), str(a.dtype))
                              for n, a in feeds.items()}
        digest = self._shape_digest(sig["feed_shapes"])
        path = os.path.join(dirname,
                            self.AOT_PREFIX + digest + self.AOT_SUFFIX)
        with open(path, "wb") as f:
            pickle.dump({"sig": sig, "payload": payload}, f)
        # integrity tag checked BEFORE unpickling at load (guards a
        # corrupted/partially-copied artifact; an adversary who can
        # rewrite the model dir can rewrite both files — the dir itself
        # must be trusted, see load_compiled). Hash the written file in
        # chunks: executables can be hundreds of MB.
        import hashlib
        h = hashlib.sha256()
        with open(path, "rb") as f:
            for chunk in iter(lambda: f.read(1 << 20), b""):
                h.update(chunk)
        with open(path + ".sha256", "w") as f:
            f.write(h.hexdigest())
        return path

    def load_compiled(self, dirname: str) -> bool:
        """Load every serialized executable in ``dirname`` that matches
        this program (one per feed-shape signature, plus the legacy
        single-file name); returns whether any loaded. Inputs matching
        no loaded signature fall back to the compile path at run() —
        counted in paddle_serving_aot_fallback_total.

        SECURITY: the artifacts are pickles (like any serialized XLA
        executable they embed callables) — ``dirname`` must be a TRUSTED
        model directory, same trust level as the model program itself.
        The sha256 sidecar written by save_compiled is verified before
        unpickling, which catches corruption/truncation; it is not a
        defense against an attacker who can write the directory."""
        import glob
        import os
        self._aot_load_attempted = True
        paths = sorted(glob.glob(os.path.join(
            dirname, self.AOT_PREFIX + "*" + self.AOT_SUFFIX)))
        legacy = os.path.join(dirname, self.AOT_FILENAME)
        if os.path.exists(legacy) and legacy not in paths:
            paths.append(legacy)
        loaded = dict(self._aot or {})
        fingerprint = self._program_fingerprint()
        for path in paths:
            entry = self._load_one_aot(path, fingerprint)
            if entry is not None:
                exe, sig = entry
                loaded[self._shape_digest(sig["feed_shapes"])] = (exe, sig)
        self._aot = loaded
        return bool(loaded)

    def _load_one_aot(self, path: str, fingerprint: str):
        import hashlib
        import os
        import pickle
        import warnings
        from jax.experimental import serialize_executable as se
        with open(path, "rb") as f:
            raw = f.read()
        digest_path = path + ".sha256"
        if os.path.exists(digest_path):
            with open(digest_path) as f:
                want = f.read().strip()
            if hashlib.sha256(raw).hexdigest() != want:
                warnings.warn(
                    f"AOT executable {os.path.basename(path)} failed its "
                    f"sha256 integrity check (corrupted or partially "
                    f"copied) — ignoring it; re-run save_compiled",
                    stacklevel=3)
                return None
        try:
            blob = pickle.loads(raw)
            sig = blob["sig"]
        except Exception:
            warnings.warn(f"AOT executable {os.path.basename(path)} is "
                          f"unreadable — ignoring it", stacklevel=3)
            return None
        # the executable bakes in the traced program INCLUDING amp/nhwc
        # rewrites — a stale artifact or a predictor configured
        # differently must not serve silently different numerics
        if sig.get("program_fingerprint") != fingerprint \
                or sig.get("fetch_names") != list(self._fetch_names):
            warnings.warn(
                f"AOT executable {os.path.basename(path)} was compiled "
                f"for a different program (graph changed or amp/nhwc "
                f"rewrites differ) — ignoring it; re-run save_compiled",
                stacklevel=3)
            return None
        try:
            # the executable was compiled for the predictor's ONE device;
            # left to its default the loader hands it every local device
            # and each call then fails on a host with several
            return se.deserialize_and_load(
                *blob["payload"],
                execution_devices=[self._exe.device]), sig
        except Exception as e:
            warnings.warn(f"AOT executable {os.path.basename(path)} "
                          f"failed to deserialize ({type(e).__name__}) — "
                          f"ignoring it", stacklevel=3)
            return None

    def _run_aot(self, inputs) -> Optional[List[np.ndarray]]:
        entry = self._aot.get(self._digest_of_inputs(inputs))
        if entry is None:
            return None                   # signature miss: compile path
        exe, sig = entry
        feeds = {n: np.asarray(inputs[n]) for n in sig["feed_shapes"]}
        state, consts, feeds = self._aot_args(sig, feeds)
        try:
            fetches, _ = exe(state, consts, feeds, np.uint32(0))
        except Exception as e:
            # some backends round-trip serialization but mis-map devices
            # on load (XLA:CPU under forced virtual device counts does) —
            # serving must degrade to the compile path, not die
            import warnings
            warnings.warn(f"AOT executable failed on this backend "
                          f"({type(e).__name__}); falling back to the "
                          f"compile path", stacklevel=3)
            self._aot = {}
            self._count_fallback("backend_error")
            return None
        return [np.asarray(o) for o in fetches]


def create_paddle_predictor(config: AnalysisConfig) -> PaddlePredictor:
    """reference: CreatePaddlePredictor<AnalysisConfig>."""
    return PaddlePredictor(config)
