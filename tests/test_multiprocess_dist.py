"""Localhost multi-process distributed training test — capability parity
with the reference's test_dist_base.py (§4: "forks real localhost
processes ... results pickled over stdout and compared"). Two OS processes
× 2 virtual CPU devices join one jax.distributed coordination service (the
gen_nccl_id replacement) and run a dp=4 training step whose gradient
all-reduce crosses the process boundary."""

import json
import os
import subprocess
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _run_workers(nprocs, model, steps, extra_env=None):
    from _dist_utils import PortReservation
    # held open until the workers exit: rank 0's gRPC coordinator
    # (SO_REUSEPORT) binds through the reservation; third parties can't
    reservation = PortReservation()
    port = reservation.port
    workers = []
    env_base = {k: v for k, v in os.environ.items()
                if not k.startswith(("PADDLE_", "XLA_FLAGS", "JAX_"))}
    for rank in range(nprocs):
        env = dict(env_base)
        env["PADDLE_COORDINATOR"] = f"127.0.0.1:{port}"
        env["PADDLE_TRAINER_ID"] = str(rank)
        env["PADDLE_TRAINERS_NUM"] = str(nprocs)
        env["PADDLE_TEST_MODEL"] = model
        env["PADDLE_TEST_STEPS"] = str(steps)
        env.update(extra_env or {})
        workers.append(subprocess.Popen(
            [sys.executable, os.path.join(os.path.dirname(__file__),
                                          "dist_worker.py")],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            cwd=os.path.dirname(os.path.dirname(__file__)), env=env,
            text=True))
    results = {}
    try:
        for rank, w in enumerate(workers):
            out, err = w.communicate(timeout=420)
            assert w.returncode == 0, f"rank {rank} failed:\n{err[-3000:]}"
            line = [l for l in out.splitlines()
                    if l.startswith("RESULT ")][-1]
            results[rank] = json.loads(line[len("RESULT "):])
    finally:
        # never leave a worker blocked in the coordination barrier
        for w in workers:
            if w.poll() is None:
                w.kill()
        reservation.close()
    return results


def test_two_process_dp_training_matches():
    results = _run_workers(2, "mlp", 12)
    l0 = results[0]["losses"]
    l1 = results[1]["losses"]
    # both processes compute the same global loss (the all-reduce crossed
    # the process boundary) and it decreases
    np.testing.assert_allclose(l0, l1, rtol=1e-5)
    assert l0[-1] < l0[0] * 0.7, l0


def test_launch_tool_runs_coordinated_workers(tmp_path):
    """tools/launch.py (the cluster-launch capability): 2 workers
    rendezvous through the coordination service it provides and see the
    4-device global mesh."""
    sys_path_root = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    script = tmp_path / "worker.py"
    script.write_text(
        "import sys\n"
        f"sys.path.insert(0, {sys_path_root!r})\n"
        "import os\n"
        "os.environ['XLA_FLAGS'] = (os.environ.get('XLA_FLAGS', '') +"
        " ' --xla_force_host_platform_device_count=2').strip()\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "from paddle_tpu import distributed\n"
        "distributed.init_parallel_env()\n"
        "print('GLOBAL', len(jax.devices()), 'RANK',\n"
        "      os.environ['PADDLE_TRAINER_ID'], flush=True)\n"
        "assert len(jax.devices()) == 4\n")
    from tools.launch import launch
    env_backup = dict(os.environ)
    try:
        rc = launch(2, [str(script)])
    finally:
        os.environ.clear()
        os.environ.update(env_backup)
    assert rc == 0


def test_two_process_sharded_table_training():
    """Embedding table row-sharded over a tp axis SPANNING the two
    processes (half the rows live on each process — the pserver-sharded-
    table capability, SURVEY §2 #24/#27) with dp inside each process;
    curves match the single-process local baseline."""
    local = _run_workers(1, "sharded_table", 10,
                         extra_env={"PADDLE_LOCAL_BASELINE": "1"})
    dist = _run_workers(2, "sharded_table", 10)
    base = local[0]["losses"]
    l0 = dist[0]["losses"]
    np.testing.assert_allclose(l0, dist[1]["losses"], rtol=1e-5)
    np.testing.assert_allclose(l0, base, rtol=2e-3, atol=2e-3)
    assert l0[-1] < l0[0] * 0.8, l0


def test_two_process_transformer_dp_loss_curve_parity():
    """The reference's model-parity method (test_dist_base.py:257-286):
    train the SAME transformer (a) single-process single-device and
    (b) dp=4 over 2 OS processes, and compare the loss CURVES step by
    step over 12 steps — not just 'loss decreased'."""
    local = _run_workers(1, "transformer", 12,
                         extra_env={"PADDLE_LOCAL_BASELINE": "1"})
    dist = _run_workers(2, "transformer", 12)
    base = local[0]["losses"]
    l0 = dist[0]["losses"]
    l1 = dist[1]["losses"]
    np.testing.assert_allclose(l0, l1, rtol=1e-5)       # cross-process
    # dist curve tracks the local curve step by step (fp reassociation
    # across the dp all-reduce allows small drift)
    np.testing.assert_allclose(l0, base, rtol=2e-3, atol=2e-3)
    assert l0[-1] < l0[0], l0


def test_merged_multi_trainer_timeline(tmp_path):
    """tools/trace_collect.py merges per-trainer span files into ONE
    chrome trace with a pid lane per trainer (reference:
    tools/timeline.py:27-30 accepts 'trainer1=f1,trainer2=f2,ps=f3') —
    the observability story for the multi-process training this suite
    exercises."""
    spans_dir = str(tmp_path)
    _run_workers(2, "mlp", 6,
                 extra_env={"PADDLE_TEST_SPANS_DIR": spans_dir})
    files = sorted(os.listdir(spans_dir))
    assert files == ["spans_rank0.csv", "spans_rank1.csv"], files

    from tools.trace_collect import merge_span_files, parse_profile_paths
    arg = ",".join(f"trainer{r}={os.path.join(spans_dir, f)}"
                   for r, f in enumerate(files))
    named = parse_profile_paths(arg)
    assert [n for n, _ in named] == ["trainer0", "trainer1"]
    trace = merge_span_files(named)

    lanes = {e["pid"] for e in trace["traceEvents"] if e["ph"] == "X"}
    assert lanes == {0, 1}
    labels = {e["pid"]: e["args"]["name"] for e in trace["traceEvents"]
              if e["ph"] == "M" and e["name"] == "process_name"}
    assert labels == {0: "trainer0", 1: "trainer1"}
    # each lane carries that rank's training span(s), no other rank's,
    # and that rank's own executor spans (recorded whenever a profiler
    # is active: executor.run with its prepare / dispatch parts; and
    # what the pause watch records while one is: a host.pause wherever
    # this machine held the process up)
    for pid, label in labels.items():
        rank_events = [e["name"] for e in trace["traceEvents"]
                       if e["ph"] == "X" and e["pid"] == pid]
        mine = [n for n in rank_events if n.startswith(f"rank{pid}/")]
        rest = [n for n in rank_events if n not in mine]
        assert mine, rank_events
        assert rest and all(n.startswith(("executor.", "compile.", "host."))
                            for n in rest), rank_events
        assert "executor.run" in rest and "executor.dispatch" in rest

    # single-file form still works (no metadata lane)
    single = merge_span_files(parse_profile_paths(
        os.path.join(spans_dir, files[0])))
    assert all(e["ph"] == "X" for e in single["traceEvents"])
