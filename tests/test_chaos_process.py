"""Process-level chaos for the distributed-tracing stack (ISSUE 12):
a SIGTERM'd process dumps its flight recorder atomically; a SIGKILLed
server's black box names the injected kill point; a trainer killed
mid-lease leaves the held lease in its black box AND its RPC spans in
the merged cross-process trace; and (slow) the two-process serving
acceptance — tools/launch.py client + server, one ``trace_collect``
command, the client's request span strictly containing the server's
admission -> prefill@bucket -> decode-step -> settle lifecycle."""

import json
import os
import signal
import subprocess
import sys
import time

import pytest

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(TESTS_DIR)

pytestmark = pytest.mark.chaos


def _env_base():
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("PADDLE_", "FLAGS_"))}
    env["JAX_PLATFORMS"] = "cpu"
    return env


def _read_jsonl(path):
    out = []
    with open(path, encoding="utf-8") as f:
        for line in f:
            try:
                out.append(json.loads(line))
            except json.JSONDecodeError:
                pass                      # torn final line of a killed proc
    return out


def _one(glob_dir, suffix):
    names = [n for n in os.listdir(glob_dir) if n.endswith(suffix)]
    assert len(names) == 1, (suffix, sorted(os.listdir(glob_dir)))
    return os.path.join(glob_dir, names[0])


def _trace_collect(mod_name="trace_collect"):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        mod_name, os.path.join(REPO_ROOT, "tools", "trace_collect.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_SIGTERM_BODY = """
import sys, time
from paddle_tpu import flags
flags.set("flight_recorder_dir", sys.argv[1])
flags.set("trace_role", "termee")
from paddle_tpu.observability import flight_recorder, tracing
assert tracing.active()
flight_recorder.note("armed", phase="steady")
print("READY", flush=True)
while True:
    time.sleep(0.05)
"""


def test_sigterm_dumps_flight_recorder(tmp_path):
    """SIGTERM: the handler dumps atomically, then the process still
    dies OF SIGTERM (honest wait status), and the dump carries the
    breadcrumbs recorded before the signal."""
    d = str(tmp_path / "rec")
    p = subprocess.Popen([sys.executable, "-c", _SIGTERM_BODY, d],
                         stdout=subprocess.PIPE, text=True,
                         cwd=REPO_ROOT, env=_env_base())
    try:
        assert p.stdout.readline().strip() == "READY"
        p.send_signal(signal.SIGTERM)
        assert p.wait(timeout=30) == -signal.SIGTERM
    finally:
        if p.poll() is None:
            p.kill()
    dump = json.load(open(_one(d, ".dump.json")))
    assert dump["reason"] == "sigterm"
    assert dump["role"] == "termee"
    kinds = [e["kind"] for e in dump["events"]]
    assert "sigterm" in kinds
    notes = [e for e in dump["events"] if e["kind"] == "note"]
    assert any(n["what"] == "armed" for n in notes)
    # the black box has the same trail, flushed line by line
    bb = _read_jsonl(_one(d, ".blackbox.jsonl"))
    assert [e for e in bb if e["kind"] == "sigterm"]


def test_sigkill_blackbox_names_kill_point(tmp_path):
    """SIGKILL mid-request: no dump hook fires, but the always-flushed
    black box survives — its last fault event IS the injected kill
    point (the serving.handle delay the kill rides on)."""
    d = str(tmp_path / "rec")
    env = _env_base()
    env["FLAGS_flight_recorder_dir"] = d
    env["FLAGS_trace_spool_dir"] = d
    env["FLAGS_trace_role"] = "victim"
    env["FLAGS_fault_plan"] = "serving.handle:delay@1:s=30"
    p = subprocess.Popen(
        [sys.executable, os.path.join(TESTS_DIR, "serving_victim.py"),
         str(tmp_path)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        cwd=REPO_ROOT, env=env)
    try:
        # stderr rides on the same pipe: XLA's own log lines (a CPU
        # compile cache made on another machine logs one per load) may
        # come before READY
        seen = []
        for line in p.stdout:
            if line.startswith("READY"):
                break
            seen.append(line)
        else:
            raise AssertionError("no READY line:\n" + "".join(seen)[-2000:])
        endpoint = line.split()[1]
        host, port = endpoint.rsplit(":", 1)
        import socket
        s = socket.create_connection((host, int(port)), timeout=10)
        s.sendall(b'{"method": "ping"}\n')
        # the fault observer records the site BEFORE the 30s delay —
        # wait for that line to hit the black box, then kill mid-delay
        bb_path = os.path.join(d, f"victim.{p.pid}.blackbox.jsonl")
        deadline = time.time() + 60
        while time.time() < deadline:
            if os.path.exists(bb_path) and any(
                    e["kind"] == "fault" for e in _read_jsonl(bb_path)):
                break
            time.sleep(0.05)
        p.kill()                           # SIGKILL: no hook, no dump
        assert p.wait(timeout=30) == -signal.SIGKILL
        s.close()
    finally:
        if p.poll() is None:
            p.kill()
    events = _read_jsonl(bb_path)
    faults_seen = [e for e in events if e["kind"] == "fault"]
    assert faults_seen, events
    assert faults_seen[-1]["site"] == "serving.handle"
    assert faults_seen[-1]["mode"] == "delay"
    # the fault fire also dumped (before its effect): the atomic dump
    # survived the SIGKILL and its last fault names the kill point too
    dump = json.load(open(
        os.path.join(d, f"victim.{p.pid}.dump.json")))
    assert dump["reason"] == "fault"
    dump_faults = [e for e in dump["events"] if e["kind"] == "fault"]
    assert dump_faults[-1]["site"] == "serving.handle"


def test_trainer_killed_mid_lease(tmp_path):
    """Kill a trainer holding a chunk lease: its black box names the
    lease, and the merged trace still shows its master.get_task span
    parented into the master's handler span (a cross-process flow
    edge) — the dump + merged-trace reconstruction of the acceptance
    criteria."""
    from _dist_utils import PortReservation
    from paddle_tpu import recordio
    d = str(tmp_path / "share")
    os.makedirs(d, exist_ok=True)
    data = str(tmp_path / "part-000.recordio")
    w = recordio.Writer(data, max_chunk_records=2)
    for i in range(8):
        w.write(f"r{i}".encode())
    w.close()

    env = _env_base()
    env["FLAGS_trace_spool_dir"] = d
    env["FLAGS_trace_role"] = "master"
    env["MASTER_SNAPSHOT"] = str(tmp_path / "snap.json")
    env["MASTER_PATHS"] = data
    env["MASTER_LEASE_S"] = "30"
    trainer = None
    with PortReservation() as r:
        env["MASTER_PORT"] = str(r.port)
        master = subprocess.Popen(
            [sys.executable, os.path.join(TESTS_DIR, "master_host.py")],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            cwd=REPO_ROOT, env=env)
        try:
            line = master.stdout.readline()
            assert line.startswith("READY"), line
            endpoint = line.split()[1]

            tenv = _env_base()
            from paddle_tpu.data.master_service import MASTER_ENV
            tenv[MASTER_ENV] = endpoint
            trainer = subprocess.Popen(
                [sys.executable,
                 os.path.join(TESTS_DIR, "lease_worker.py"), d],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True, cwd=REPO_ROOT, env=tenv)
            line = trainer.stdout.readline()
            assert line.startswith("LEASED"), line
            task_id = int(line.split()[1])
            trainer.kill()                 # mid-lease SIGKILL
            assert trainer.wait(timeout=30) == -signal.SIGKILL
        finally:
            master.terminate()
            master.wait(timeout=30)
            if trainer is not None and trainer.poll() is None:
                trainer.kill()

    bb = _read_jsonl(_one(d, ".blackbox.jsonl"))
    leases = [e for e in bb if e["kind"] == "note"
              and e["what"] == "lease_taken"]
    assert leases and leases[-1]["task"] == task_id
    # merged trace: the trainer's get_task span and the master's handler
    # span share a trace, stitched by a cross-process flow edge
    tc = _trace_collect()
    evs = tc.merge(tc.find_spools(d))["traceEvents"]
    gets = [e for e in evs if e.get("ph") == "X"
            and e["name"] == "master.get_task"]
    assert len(gets) >= 2                  # client side + server side
    assert len({e["pid"] for e in gets}) == 2
    assert [e for e in evs if e.get("ph") == "s"]


@pytest.mark.slow
def test_two_process_serving_acceptance(tmp_path):
    """The ISSUE 12 acceptance: launch a real ServingClient process and
    a real ModelServer process with tools/launch.py, run ONE
    ``trace_collect`` command over the spools, and verify the client's
    request span strictly contains the server's admission ->
    prefill@bucket -> decode-step -> settle spans via propagated
    context, with >=1 flow event per cross-process edge."""
    d = str(tmp_path / "share")
    os.makedirs(d, exist_ok=True)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "tools", "launch.py"),
         "--nprocs", "2", "--use-cpu",
         os.path.join(TESTS_DIR, "serving_duo.py"), d],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        cwd=REPO_ROOT, env=_env_base(), timeout=900)
    assert r.returncode == 0, r.stdout[-4000:]
    trace_id = next(line.split()[-1] for line in r.stdout.splitlines()
                    if "TRACE_ID" in line)
    assert len(trace_id) == 32

    # the one command
    rc = subprocess.run(
        [sys.executable,
         os.path.join(REPO_ROOT, "tools", "trace_collect.py"), d],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        cwd=REPO_ROOT, env=_env_base(), timeout=120)
    assert rc.returncode == 0, rc.stdout
    assert os.path.exists(os.path.join(d, "trace.json"))
    chk = subprocess.run(
        [sys.executable,
         os.path.join(REPO_ROOT, "tools", "trace_collect.py"), d,
         "--check"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        cwd=REPO_ROOT, env=_env_base(), timeout=120)
    assert chk.returncode == 0, chk.stdout

    tc = _trace_collect()
    spools = {os.path.basename(p).split(".")[0]: p
              for p in tc.find_spools(d)}
    _, client_spans, _ = tc.load_spool(spools["client"])
    _, server_spans, _ = tc.load_spool(spools["server"])
    req = next(s for s in client_spans
               if s["name"] == "serving.generate"
               and s.get("trace_id") == trace_id)
    mine = [s for s in server_spans if s.get("trace_id") == trace_id]
    names = {s["name"] for s in mine}
    assert "serving.admission" in names, names
    assert any(n.startswith("serving.prefill@") for n in names), names
    assert "serving.decode_step" in names, names
    assert "serving.settle" in names, names
    for s in mine:
        assert s["ts"] >= req["ts"] - 1.0, (s["name"], s["ts"], req)
        assert s["ts"] + s["dur"] <= req["ts"] + req["dur"] + 1.0, \
            (s["name"], s, req)
    # >=1 flow event per cross-process edge in the merged trace
    evs = json.load(open(os.path.join(d, "trace.json")))["traceEvents"]
    starts = [e for e in evs if e.get("ph") == "s"]
    finishes = [e for e in evs if e.get("ph") == "f"]
    assert starts and len(starts) == len(finishes)
