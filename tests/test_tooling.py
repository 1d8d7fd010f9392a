"""Debug tooling tests: graphviz dump, timeline export, nan/inf checker
(reference: debugger.py draw_block_graphviz, tools/timeline.py,
FLAGS_check_nan_inf operator.cc:978)."""

import json
import os

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import debugger, profiler


def _small_program():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        y = fluid.layers.fc(x, 2, act="relu")
        loss = fluid.layers.mean(y)
    return main, startup, loss


def test_draw_block_graphviz(tmp_path):
    main, _, _ = _small_program()
    path = str(tmp_path / "g.dot")
    dot = debugger.draw_program(main, path=path)
    assert dot.startswith("digraph")
    assert "mul" in dot and "reduce" in dot.lower() or "mean" in dot
    assert os.path.exists(path)


def test_profiler_timeline_export(tmp_path):
    main, startup, loss = _small_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    trace = str(tmp_path / "trace.json")
    with profiler.profiler():
        with profiler.record_event("train_step"):
            exe.run(main, feed={"x": np.ones((2, 4), np.float32)},
                    fetch_list=[loss.name])
        profiler.export_chrome_trace(trace)
    data = json.load(open(trace))
    names = [e["name"] for e in data["traceEvents"]]
    assert "train_step" in names


def test_device_op_stats(tmp_path):
    """Per-HLO-op device-time attribution from a jax.profiler trace —
    the CUPTI DeviceTracer capability (platform/device_tracer.h:39) that
    host spans can't provide once exe.run(iterations=N) makes the whole
    window one dispatch.

    The trace is captured in a clean subprocess (env-selected cpu
    backend), away from whatever this process has already traced."""
    import subprocess
    import sys

    # device_op_stats reduces the trace with xprof's converters
    pytest.importorskip(
        "xprof", reason="xprof (the trace converters) is not installed")

    d = str(tmp_path / "devtrace")
    # raw jit payload: on the CPU backend, xprof's hlo_stats aggregates
    # the XLA:CPU op events only for directly-jitted computations (the
    # executor's scan-wrapped run shows the ops in trace_viewer but not
    # hlo_stats); on TPU both paths aggregate — the capture side of
    # exe.run(iterations=N) + device_op_stats is exercised on real
    # hardware (STATUS.md transformer/resnet profiles used exactly that)
    script = f"""
import numpy as np, jax, jax.numpy as jnp
from paddle_tpu.fluid import profiler
f = jax.jit(lambda a: jnp.tanh(a @ a))
x = jnp.ones((256, 256))
np.asarray(f(x))
profiler.start_profiler(trace_dir={d!r})
for _ in range(4):
    x = f(x)
np.asarray(x)
profiler.stop_profiler(trace_dir={d!r})
"""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("XLA_FLAGS", "JAX_"))}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=300,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert r.returncode == 0, r.stderr[-2000:]
    # hlo_stats reduces the trace's DEVICE planes; this jax's XLA:CPU
    # trace holds only '/host:CPU', so on a machine without a chip there
    # is nothing to attribute (the chip path: chipbench --trace 1)
    import glob
    import jax
    (pb,) = glob.glob(d + "/plugins/profile/*/*.xplane.pb")
    planes = [p.name for p in jax.profiler.ProfileData.from_file(pb).planes]
    if not any(n.startswith("/device:") for n in planes):
        pytest.skip(f"the trace has no device plane (planes: {planes})")
    rows = profiler.device_op_stats(d)
    assert rows and all("self_time_us" in r for r in rows)
    assert rows == sorted(rows, key=lambda r: -r["self_time_us"])
    top = profiler.print_device_op_stats(d, top=3)
    assert len(top) <= 3


def test_check_nan_inf_flag(monkeypatch):
    monkeypatch.setenv("FLAGS_check_nan_inf", "1")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[2], dtype="float32")
        y = fluid.layers.log(x)        # log of negative → nan
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    with pytest.raises(FloatingPointError, match="check_nan_inf"):
        exe.run(main, feed={"x": np.array([[-1.0, 2.0]], np.float32)},
                fetch_list=[y.name])
    # clean input passes
    out = exe.run(main, feed={"x": np.array([[1.0, 2.0]], np.float32)},
                  fetch_list=[y.name])
    assert np.isfinite(out[0]).all()


def test_kube_gen_job_yaml():
    """Cluster fan-out template (round-2 verdict item 10; reference:
    benchmark/fluid/kube_gen_job.py): generated yaml carries an Indexed
    Job + headless Service with the PADDLE_* env convention."""
    import sys
    import os
    import yaml
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    import kube_gen_job as kg
    args = kg.parse_args(["--jobname", "tj", "--trainers", "4",
                          "--image", "img:1", "--tpu", "4",
                          "--tpu-topology", "2x2",
                          "--entry", "python t.py",
                          "--env", "FLAGS_check_nan_inf=1"])
    svc, job = kg.gen_all(args)
    # round-trip through yaml like kubectl would consume it
    svc, job = yaml.safe_load(yaml.safe_dump(svc)), \
        yaml.safe_load(yaml.safe_dump(job))
    assert svc["kind"] == "Service" and svc["spec"]["clusterIP"] == "None"
    assert job["spec"]["completionMode"] == "Indexed"
    assert job["spec"]["completions"] == 4
    pod = job["spec"]["template"]["spec"]
    assert pod["subdomain"] == "tj"
    env = {e["name"]: e for e in pod["containers"][0]["env"]}
    assert env["PADDLE_COORDINATOR"]["value"] == "tj-0.tj:9876"
    assert env["PADDLE_TRAINERS_NUM"]["value"] == "4"
    assert "job-completion-index" in str(env["PADDLE_TRAINER_ID"])
    assert env["FLAGS_check_nan_inf"]["value"] == "1"
    res = pod["containers"][0]["resources"]["limits"]
    assert res["google.com/tpu"] == "4"
    assert pod["nodeSelector"]["cloud.google.com/gke-tpu-topology"] == "2x2"


def test_api_spec_matches():
    """API-stability gate (reference: paddle/fluid/API.spec +
    tools/diff_api.py in CI): the committed spec matches the live API;
    intentional changes must regenerate it (--update)."""
    import sys
    import os
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    import diff_api
    assert os.path.exists(diff_api.SPEC_PATH)
    removed, added = diff_api.spec_diff()
    assert not removed and not added, (removed[:10], added[:10])
