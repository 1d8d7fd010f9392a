"""The no-hidden-fallback repairs behind chip_smoke.py, and the smoke's
own CPU rehearsal: a place the host cannot honour raises, the compile
cache sits where it was told (or at one fixed path), an unknown
accelerator has no assumed peak, and a CPU run of the smoke can never
be mistaken for a chip run."""

import json
import os
import subprocess
import sys

import jax
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.core.executor import _resolve_device

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- places ----------------------------------------------------------------

def test_resolve_device_honours_or_raises():
    devs = jax.devices()                  # conftest: 8 virtual CPU devices
    assert _resolve_device(fluid.TPUPlace()) == devs[0]
    assert _resolve_device(fluid.TPUPlace(3)) == devs[3]
    assert _resolve_device(fluid.CPUPlace()) == jax.devices("cpu")[0]
    for bad in (len(devs), -1):
        with pytest.raises(ValueError, match="device"):
            _resolve_device(fluid.TPUPlace(bad))


def test_feedless_program_runs_on_the_place():
    """A startup program has no feeds to follow: it must still land on
    the place's device, not on the process default."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        fluid.layers.fc(x, 3, param_attr=fluid.ParamAttr(name="w_on_3"))
    scope = fluid.Scope()
    fluid.Executor(fluid.TPUPlace(3)).run(startup, scope=scope)
    assert scope.find_var("w_on_3").devices() == {jax.devices()[3]}


# -- peaks -----------------------------------------------------------------

class _FakeDevice:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


def test_unknown_accelerator_has_no_assumed_peak():
    from paddle_tpu.utils import flops
    assert flops.device_peak_flops(_FakeDevice("tpu", "TPU v5 lite")) \
        == 197e12
    assert flops.device_peak_flops(_FakeDevice("cpu", "cpu")) is None
    for lookup in (flops.device_peak_flops, flops.device_peak_hbm):
        with pytest.raises(KeyError, match="TPU v99"):
            lookup(_FakeDevice("tpu", "TPU v99"))


# -- the compile cache ------------------------------------------------------

_CACHE_PROBE = (
    "import jax\n"
    "from paddle_tpu.utils import chip\n"
    "print(chip.compile_cache_dir())\n"
    "print(jax.config.jax_compilation_cache_dir)\n")


def _probe_cache(env_dir):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", _CACHE_PROBE], cwd=REPO,
                         env=env, capture_output=True, text=True,
                         timeout=120, check=True).stdout.split()
    return out


def test_compile_cache_honours_the_environment(tmp_path):
    """Set from outside: JAX's own handling is the whole story — the
    function reports the directory and configures nothing else."""
    want = str(tmp_path / "cache")
    said, configured = _probe_cache(want)
    assert said == want and configured == want


def test_compile_cache_default_is_one_fixed_path():
    """Unset: the same in-checkout path from two processes in a row
    (the path is part of the cache key — a tempdir never hits)."""
    fixed = os.path.join(REPO, ".jax_cache")
    assert _probe_cache(None) == [fixed, fixed]
    assert _probe_cache(None) == [fixed, fixed]


# -- the smoke itself -------------------------------------------------------

def _run_smoke(*args, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("XLA_FLAGS", None)            # one CPU device, as a user has
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py"),
                        *args], cwd=REPO, env=env, capture_output=True,
                       text=True, timeout=600)
    return r, json.loads(r.stdout.strip().splitlines()[-1])


def test_smoke_refuses_the_cpu(tmp_path):
    r, last = _run_smoke(tmp_path=tmp_path)
    assert r.returncode != 0
    assert last == {"ok": False, "device": {"platform": "cpu",
                                            "kind": "cpu", "count": 1}}
    assert "[parity]" not in r.stdout     # nothing ran past the assert


def test_smoke_rehearsal_passes_and_says_cpu(tmp_path):
    r, last = _run_smoke("--rehearse", tmp_path=tmp_path)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert last == {"ok": True, "device": {"platform": "cpu",
                                           "kind": "cpu", "count": 1}}
    for variant in ("paged_fp32", "paged_int8"):
        assert f'"variant": "{variant}"' in r.stdout
    assert '"variant": "contiguous"' not in r.stdout
    assert r.stdout.count('"compiles_after_warm_up": 0') == 2
