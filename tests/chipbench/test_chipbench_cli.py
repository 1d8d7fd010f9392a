"""The command line refuses anything but the chip: exit code other than
0 and no result line on a CPU, and in a directory that holds only the
benchmark's own files."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)


def run_cli(cwd, cell):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_RUN": "7"}
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        BENCH["command"] + ["--workload", cell, "--seed", "2147483999",
                            "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def has_result_line(stdout):
    for line in stdout.splitlines():
        try:
            if "metrics" in json.loads(line):
                return True
        except ValueError:
            continue
    return False


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cli_refuses_a_cpu(cell):
    out = run_cli(ROOT, cell)
    assert out.returncode != 0
    assert not has_result_line(out.stdout)
    assert "no accelerator" in out.stderr


def test_cli_refuses_a_directory_with_the_benchmark_alone(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = run_cli(str(tmp_path), BENCH["workloads"][0]["name"])
    assert out.returncode != 0 and not has_result_line(out.stdout)


def test_cli_refuses_an_unknown_cell():
    out = run_cli(ROOT, "no_such_cell")
    assert out.returncode != 0 and not has_result_line(out.stdout)


def test_the_command_names_nothing_outside_paths():
    assert BENCH["command"][:3] == ["python3", "-m", "chipbench"]
    assert sys.version_info >= (3, 9)
