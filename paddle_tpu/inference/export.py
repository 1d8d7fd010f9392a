"""Portable-IR export (reference capability: save_inference_model's
serialized ProgramDesc as the deployment format, io.py:570 + the C++
inference loader inference/io.cc. TPU-native form: StableHLO — the
portable XLA input dialect any PJRT serving stack consumes)."""

from __future__ import annotations

import os
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np


def export_stablehlo(dirname: str, feed_shapes: Dict[str, Tuple],
                     executor=None, out_path: Optional[str] = None,
                     scope=None):
    """Lower a saved inference model (save_inference_model output at
    `dirname`) to StableHLO text + a jax.export serialized artifact.

    feed_shapes: {feed name: concrete shape} — XLA needs static shapes, so
    the export is per input signature (the reference's TRT engines were
    likewise built per optimization profile).

    Returns (stablehlo_text_path, serialized_path)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.core.lowering import analyze_block, build_block_fn

    scope = scope or fluid.Scope()
    exe = executor or fluid.Executor(fluid.TPUPlace())
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        program, feeds, fetches = fluid.io.load_inference_model(
            dirname, exe, scope=scope)

    sig = analyze_block(program.desc.global_block, feeds, fetches)
    fn = build_block_fn(program.desc, 0, sig, is_test=True)

    state = {n: scope.find_var(n) for n in sig.state_names}
    consts = {n: scope.find_var(n) for n in sig.const_names}

    def infer(feed_arrays):
        fetch_vals, _ = fn(state, consts, feed_arrays, np.uint32(0))
        return fetch_vals

    example = {
        n: jax.ShapeDtypeStruct(
            tuple(feed_shapes[n]),
            np.dtype(program.desc.global_block.var(n).dtype
                     if program.desc.global_block.has_var(n)
                     else "float32"))
        for n in feeds}

    # single trace: jax.export both serializes and carries the StableHLO
    # module text, so the model is lowered exactly once
    jitted = jax.jit(infer)
    out_path = out_path or os.path.join(dirname, "model.stablehlo")
    ser_path = out_path + ".bin"
    try:
        from jax import export as jax_export
        exported = jax_export.export(jitted)(example)
        text = exported.mlir_module()
        with open(ser_path, "wb") as f:
            f.write(exported.serialize())
    except Exception:   # jax.export unsupported on this jax build
        ser_path = None
        text = jitted.lower(example).as_text(dialect="stablehlo")
    with open(out_path, "w") as f:
        f.write(text)
    return out_path, ser_path


def write_runner_bundle(bundle_dir: str, stablehlo_path: str,
                        feed_arrays: Dict[str, np.ndarray]):
    """Self-contained bundle for the NON-PYTHON serving consumer
    (csrc/stablehlo_runner.cc — the reference's C++ predictor capability,
    inference/api/paddle_api.h): the StableHLO module, a serialized
    CompileOptionsProto, and a manifest + raw input tensors in the
    executable's argument order (jax.export flattens the feed dict in
    sorted-key order)."""
    os.makedirs(bundle_dir, exist_ok=True)
    import shutil
    shutil.copy(stablehlo_path, os.path.join(bundle_dir,
                                             "model.stablehlo"))
    from jax._src.lib import xla_client
    with open(os.path.join(bundle_dir, "compile_options.pb"), "wb") as f:
        f.write(xla_client.CompileOptions().SerializeAsString())
    lines = []
    for i, name in enumerate(sorted(feed_arrays)):
        arr = np.ascontiguousarray(feed_arrays[name])
        fname = f"in_{i}.bin"
        arr.tofile(os.path.join(bundle_dir, fname))
        dims = " ".join(str(d) for d in arr.shape)
        lines.append(f"input {name} {arr.dtype.name} {arr.ndim} "
                     f"{dims} {fname}".replace("  ", " "))
    with open(os.path.join(bundle_dir, "manifest.txt"), "w") as f:
        f.write("\n".join(lines) + "\n")
    return bundle_dir
