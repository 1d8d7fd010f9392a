"""chipbench/layer_metrics/scope_ms.py on a recorded decode step of
``serve_solar_decode_closed`` with the program's map of it
(data/scope_trace.json: my chip run, PR 35) and on hand-made traces:
exact values, 0.0 against None, the ``jit_copy`` snapshots that are not
steps, the listing, and the contract of the metrics that use it."""

import json
import os
import re
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from chipbench import harness  # noqa: E402
from chipbench import trace_reduce as tr  # noqa: E402
from chipbench.layer_metrics import device_ms_per_unit, scope_ms  # noqa: E402

MS = 1e6
SPAN = "serving.decode_step"
DECODE = r"jit_\w+_decode_paged(_s[0-9a-f]{4})?"
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
NEW = [m for m in BENCH["per_layer"]
       if harness.load_json("layer_metrics", m["name"] + ".json")["reader"]
       == "scope_ms"]


def recorded():
    with open(os.path.join(HERE, "data", "scope_trace.json")) as f:
        return json.load(f)


def observations(rec, monkeypatch, executions=2, copies=0, scopes="map"):
    """The recorded step laid out as ``executions`` decode executions
    back to back under their spans, each followed by ``copies``
    ``jit_copy`` executions under the same span (the engine's snapshot
    of the expert counters), and one prefill; the program's map is the
    recorded one (``scopes="map"``), an empty one, or none at all."""
    module = rec["module"]
    events, modules, spans, table = [], [], [], {module: {}}
    at = 1.0
    number = 0
    for _ in range(executions):
        start = at
        for scope, stem, what, ms, count in rec["groups"]:
            for i in range(max(int(round(count)), 1)):
                # instruction numbers are the position in the step: the
                # same in every execution, as in a compiled module
                name = f"{stem}.{number + i}"
                table[module][name] = scope
                d = ms / max(int(round(count)), 1)
                events.append([f"{name} {what} ", at * MS, d * MS])
                at += d
            number += max(int(round(count)), 1)
        number = 0
        modules.append([f"{module}(17)", start * MS, (at - start) * MS])
        end = at
        for _ in range(copies):
            events.append(["copy.1 copy s32[2,40] ", at * MS, 0.001 * MS])
            modules.append(["jit_copy(5)", at * MS, 0.001 * MS])
            at += 0.002
        spans.append((SPAN, (start - 0.2) * MS, at * MS))
        at = end + 0.5
    events.append(["fusion.3 fusion f32[512,4096] ", at * MS, 2.0 * MS])
    modules.append(["jit_lm_prefill_paged_512_s5dba(9)", at * MS, 2.0 * MS])
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": tr.OPS_LINE, "events": events},
        {"name": tr.MODULES_LINE, "events": modules}]}]}
    obs = {"reduced": tr.reduce_window(trace, 0.0, (at + 3.0) * MS, spans),
           "units": {"decode_steps": executions, "prefills": 1},
           "config": {"name": "not-a-cell"}, "traffic": {}}
    program = {"map": (table, {"seconds": 0.1}),
               "empty": ({module: {}}, {"seconds": 0.0}),
               "none": (None, None)}[scopes]
    monkeypatch.setattr(scope_ms, "program_scopes", lambda: program)
    return obs


def of_scope(rec, *prefixes):
    return sum(ms for scope, _stem, _what, ms, _n in rec["groups"]
               if any(f"/{p}/" in f"/{scope}/" for p in prefixes))


def test_exact_values_on_the_recorded_step(monkeypatch):
    rec = recorded()
    obs = observations(rec, monkeypatch)
    step = sum(g[3] for g in rec["groups"])
    read = lambda **a: scope_ms.read(obs, module=DECODE, **a)  # noqa: E731
    ms = lambda *s: read(what="ms", unit="decode_steps",      # noqa: E731
                         scopes=list(s))
    assert read(what="busy") == pytest.approx(step, rel=1e-9)
    assert ms("kda_decode") == pytest.approx(of_scope(rec, "kda_decode"))
    assert ms("expert_ffn_held") == pytest.approx(
        of_scope(rec, "expert_ffn_held"))
    assert ms("kv_attention_decode_paged", "mla_decode_paged") \
        == pytest.approx(of_scope(rec, "kv_attention_decode_paged"))
    # whole components: a phase is not its op, an op holds its phases
    assert ms("kda_decode/state") == pytest.approx(
        of_scope(rec, "kda_decode/state"))
    assert ms("kda_decode/state") < ms("kda_decode")
    assert ms("decode") == 0.0 and ms("kda_decode/sta") == 0.0
    unscoped = sum(g[3] for g in rec["groups"] if g[0] == "")
    assert read(what="unscoped_pct") == pytest.approx(100 * unscoped / step)
    # one partition of the step's ops: the named groups, the other
    # scoped ops and the unscoped ones are the step
    tops = {g[0].split("/")[0] for g in rec["groups"]} - {""}
    assert sum(ms(t) for t in tops) + unscoped == pytest.approx(step)
    # what the chip run itself read (all groups are in the recording)
    for name, value in rec["readings"].items():
        spec = harness.load_json("layer_metrics", name + ".json")
        assert scope_ms.read(obs, **spec["args"]) == pytest.approx(
            value, rel=2e-3), name


def test_zero_where_nothing_matches_none_where_there_is_no_map(monkeypatch):
    rec = recorded()
    obs = observations(rec, monkeypatch)
    args = dict(module=DECODE, what="ms", unit="decode_steps")
    # the map exists, the scope is not in this model: 0.0
    assert scope_ms.read(obs, scopes=["mla_decode_paged"], **args) == 0.0
    # no such module in the window, no steps counted: nothing to read
    assert scope_ms.read(obs, what="busy", module="jit_nothing") is None
    assert scope_ms.read({**obs, "units": {"decode_steps": 0}},
                         scopes=["kda_decode"], **args) is None
    # a map that lacks every instruction: all of it is unscoped
    obs = observations(rec, monkeypatch, scopes="empty")
    assert scope_ms.read(obs, scopes=["kda_decode"], **args) == 0.0
    assert scope_ms.read(obs, what="unscoped_pct", module=DECODE) \
        == pytest.approx(100.0)
    # a program without device scopes (the parent): None, left out;
    # the step's busy time needs no map, only the module's name
    obs = observations(rec, monkeypatch, scopes="none")
    assert scope_ms.read(obs, scopes=["kda_decode"], **args) is None
    assert scope_ms.read(obs, what="unscoped_pct", module=DECODE) is None
    assert scope_ms.read(obs, what="busy", module=DECODE) > 0
    with pytest.raises(ValueError):
        scope_ms.read(observations(rec, monkeypatch), what="p99",
                      module=DECODE)


def test_a_program_without_the_module_gives_nothing(monkeypatch):
    """The parent commit under this PR's benchmark files."""
    import paddle_tpu.observability as package
    import paddle_tpu.observability.device_scopes  # noqa: F401
    monkeypatch.delattr(package, "device_scopes")
    monkeypatch.setitem(sys.modules,
                        "paddle_tpu.observability.device_scopes", None)
    assert scope_ms.program_scopes() == (None, None)


def test_jit_copy_executions_are_not_steps(monkeypatch):
    """Four snapshot copies under each decode-step span (PERF.md section
    7 (11)): the reader that counts executions under the spans divides
    by five, this one by the module's name."""
    rec = recorded()
    step = sum(g[3] for g in rec["groups"])
    obs = observations(rec, monkeypatch, executions=3, copies=4)
    assert scope_ms.read(obs, what="busy", module=DECODE) \
        == pytest.approx(step, rel=1e-9)
    old = device_ms_per_unit.read(obs, span_prefix=SPAN)
    assert old == pytest.approx((step + 0.004) / 5, rel=1e-6)
    assert scope_ms.read(obs, what="ms", module=DECODE, unit="decode_steps",
                         scopes=["kda_decode"]) == pytest.approx(
        of_scope(rec, "kda_decode"))


def test_two_devices_are_averaged(monkeypatch):
    rec = recorded()
    obs = observations(rec, monkeypatch)
    red = obs["reduced"]
    half = [[n, s, d / 2] for n, s, d in red["devices"]["/device:TPU:0"]]
    red["devices"]["/device:TPU:1"] = half
    red["modules"]["/device:TPU:1"] = red["modules"]["/device:TPU:0"]
    one = of_scope(rec, "kda_decode")
    assert scope_ms.read(obs, what="ms", module=DECODE, unit="decode_steps",
                         scopes=["kda_decode"]) == pytest.approx(0.75 * one)


def test_listing_and_its_printer(monkeypatch, tmp_path):
    rec = recorded()
    obs = observations(rec, monkeypatch, executions=2, copies=4)
    doc = scope_ms.listing(obs, "a_cell")
    json.dumps(doc)                               # plain data
    step = sum(g[3] for g in rec["groups"])
    decode = doc["modules"][rec["module"]]
    assert decode["executions"] == 2 and decode["mapped"]
    assert decode["busy_ms_per_execution"] == pytest.approx(step)
    assert decode["ops_ms_per_execution"] == pytest.approx(step)
    assert doc["modules"]["jit_copy"]["executions"] == 8
    assert not doc["modules"]["jit_copy"]["mapped"]
    assert doc["modules"]["jit_lm_prefill_paged_512_s5dba"][
        "busy_ms"] == pytest.approx(2.0)
    state = decode["scopes"]["kda_decode/state"]
    assert state["ms"] == pytest.approx(of_scope(rec, "kda_decode/state"))
    biggest = max((g for g in rec["groups"] if g[0] == "kda_decode/state"),
                  key=lambda g: g[3])
    assert state["groups"][0] == [biggest[1], biggest[2],
                                  pytest.approx(biggest[3]),
                                  pytest.approx(round(biggest[4]))]
    # scopes by time, largest first; the stems carry no numbers
    times = [s["ms"] for s in decode["scopes"].values()]
    assert times == sorted(times, reverse=True)
    assert not [g for s in decode["scopes"].values() for g in s["groups"]
                if re.search(r"\.\d+$", g[0])]
    path = tmp_path / "a_cell.json"
    path.write_text(json.dumps(doc))
    out = subprocess.run(
        [sys.executable, "-m", "chipbench.layer_metrics.scope_ms",
         str(path)], cwd=ROOT, capture_output=True, text=True, check=True)
    assert "kda_decode/state" in out.stdout
    assert rec["module"] + ": 2.0 executions" in out.stdout
    assert "jit_copy: 8.0 executions" in out.stdout and "[no map]" in \
        out.stdout


def test_listing_is_written_for_a_cell_of_the_benchmark(monkeypatch,
                                                        tmp_path):
    rec = recorded()
    obs = observations(rec, monkeypatch)
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(BENCH))
    scope_ms.read(obs, what="busy", module=DECODE)
    assert not os.path.exists(tmp_path / "chiprun_out")     # not a cell
    cell = next(w for w in BENCH["workloads"]
                if w["name"] == "serve_solar_decode_closed")
    obs = observations(rec, monkeypatch)
    obs["config"] = {"name": cell["config"]}
    obs["traffic"] = harness.load_json("traffic", cell["traffic"] + ".json")
    assert scope_ms.cell_of(obs) == cell["name"]
    scope_ms.read(obs, what="busy", module=DECODE)
    with open(tmp_path / "chiprun_out" / "scopes"
              / (cell["name"] + ".json")) as f:
        doc = json.load(f)
    assert doc["cell"] == cell["name"] and rec["module"] in doc["modules"]
    assert doc["map_build"] == {"seconds": 0.1}


# ------------------------------------------------- the metrics' contract

MODULES = {"serve_decode_closed": "jit_lm_decode_paged_s0b24",
           "serve_solar_decode_closed": "jit_lm_decode_paged_s1225",
           "serve_glm5_decode_longctx": "jit_lm_decode_paged_sf6d7",
           "train_big_1chip": "jit_block7_x8", "train_big_dp4": "jit_block7_x8"}
NOT_STEPS = ["jit_copy", "jit_fn", "jit_lm_prefill_paged_256",
             "jit_lm_prefill_paged_512_s5dba", "jit_block2", "jit_block7",
             "jit_lm_decode_verify_paged_s3f1a", "jit__lambda_"]


def test_the_new_metrics_are_the_ten_the_issue_lists():
    assert sorted(m["name"] for m in NEW) == sorted([
        "decode_busy_ms_per_step", "attn_ms_per_step.decode",
        "index_ms_per_step.decode", "experts_ms_per_step.decode",
        "state_ms_per_step.decode", "sample_ms_per_step.decode",
        "unscoped_pct.decode", "backward_ms_per_step.train",
        "optimizer_ms_per_step.train", "unscoped_pct.train"])


@pytest.mark.parametrize("metric", NEW, ids=lambda m: m["name"])
def test_scope_metric_contract(metric):
    from paddle_tpu.core.registry import OPS
    from paddle_tpu.observability import device_scopes
    assert metric["source"] == "device_trace" and metric["workloads"]
    assert metric["better"] == "lower"
    assert metric["unit"] == ("%" if "_pct" in metric["name"] else "ms")
    args = harness.load_json("layer_metrics", metric["name"] + ".json")[
        "args"]
    assert set(args) <= {"what", "module", "scopes", "unit"}
    assert args["what"] in ("busy", "ms", "unscoped_pct")
    train = metric["name"].endswith(".train")
    assert metric["moves"] == ("train_tokens_per_s_chip" if train
                               else "serve_tokens_per_s")
    # the module it reads is the cell's step and nothing else that runs
    wanted = re.compile(args["module"])
    for cell in metric["workloads"]:
        assert wanted.fullmatch(MODULES[cell]), cell
    assert not [m for m in NOT_STEPS if wanted.fullmatch(m)]
    if args["what"] == "ms":
        assert args["unit"] == ("steps" if train else "decode_steps")
        for scope in args["scopes"]:
            op, *phases = scope.split("/")
            assert op in OPS or op == device_scopes.GRAD, scope
            assert all(p in device_scopes.PHASES[op] for p in phases), scope
    else:
        assert "scopes" not in args and "unit" not in args
