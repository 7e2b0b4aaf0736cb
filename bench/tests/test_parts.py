"""CPU checks of the reduction by part (``bench/parts.py``), of the pinned
reduction by stage it must leave alone, of the per-part readers, and of
the resolver's sweep counter read after a window (``bench/counters.py``)."""
from __future__ import annotations

import dataclasses
import gzip
import json
import re
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import parts, run, tracing  # noqa: E402
from bench_plans import plan_of  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
LOOP = "jit(run)/round_loop/while/body/closed_call"


def _fixture():
    with gzip.open(DATA / "v5e_fleet_events.json.gz", "rt") as fh:
        return json.load(fh)


# ---------------------------------------------------------------------------
# The reduction by stage, pinned
# ---------------------------------------------------------------------------

def test_recorded_trace_stage_reduction_is_pinned():
    """What ``reduce_events`` reads from the recorded chip trace, to the
    last digit: the per-stage metrics must not move with the parts."""
    red = tracing.reduce_events(_fixture())
    assert red["busy_s"] == 0.008937375000000001
    assert red["stage_s"] == {
        "other": 0.0068432960000000004, "allocate": 1.875e-06,
        "associate": 7.9904e-05, "train": 0.001020304,
        "schedule": 0.000983935, "eval": 8.063e-06}
    assert red["device_ops"] == [
        ["other/%while.384 = (s32[]{:T(128)}, f32[2,128]{1,0:T(2",
         0.004999525],
        ["other/%copy.382 = bf16[2,64,1200,784]{3,2,1,0:T(8,128)",
         0.00123933],
        ["train/%fusion.692 = (bf16[2,64,384,784]{3,2,1,0:T(8,12",
         0.000691526],
        ["schedule/%fusion.713 = (f32[2]{0:T(128)S(1)}, f32[2,4]{1,",
         0.000550509],
        ["schedule/%reduce.669 = f32[2]{0:T(128)S(1)} reduce(f32[2,",
         0.00025478800000000004],
        ["other/%copy.800 = f32[2,64,784,128]{3,2,1,0:T(8,128)} ",
         0.000158562],
        ["schedule/%add_maximum_fusion.11 = f32[2,4]{1,0:T(2,128)S(",
         0.00012424000000000002],
        ["other/%bitcast_dynamic-update-slice_fusion.10 = bf16[3",
         9.7445e-05],
        ["other/%bitcast_dynamic-update-slice_fusion.9 = bf16[32",
         6.38e-05],
        ["other/%pad_maximum_fusion.6 = bf16[2,16,1200,784]{3,2,",
         5.9735e-05]]


# ---------------------------------------------------------------------------
# Parts of a scope path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scope,key", [
    (f"{LOOP}/train/sgd/transpose(jvp(kbd,kdh->kbh))/dot_general",
     "train/sgd"),
    (f"{LOOP}/train/while/body/closed_call/cohort/jit(take_along_axis)/"
     "gather", "train/cohort"),
    (f"{LOOP}/train/gather", "train/rest"),
    (f"{LOOP}/train/agg/while/body/cohort/gather", "train/cohort"),
    (f"{LOOP}/schedule/pdd/jit(pdd_schedule)/while", "schedule/pdd"),
    (f"{LOOP}/associate/while/body/top_k", "associate/rest"),
    ("jit(run)/round_loop/while", "other/round_loop"),
    ("jit(run)/vmap()/round_loop/while/body/dynamic_update_slice",
     "other/round_loop"),
    ("copy.382", "other/rest"),
])
def test_part_of_takes_the_innermost_part_inside_the_stage(scope, key):
    assert parts.part_of(scope) == key
    assert key.split("/")[0] == tracing.stage_of(scope)


def test_part_names_are_the_programs():
    from repro.telemetry import spans
    for name in spans.PARTS:
        assert parts.part_of(f"{LOOP}/train/{name}/dot_general") \
            == f"train/{name}"
    assert not set(spans.PARTS) & set(tracing.STAGES)


# ---------------------------------------------------------------------------
# The fallback for ops with no metadata
# ---------------------------------------------------------------------------

def _meta(path):
    return f', metadata={{op_name="{path}" source_file="x.py" source_line=1}}'


# A compiled module in the form the TPU's compiler prints: layouts on the
# shapes, operands with their shapes, metadata only where XLA kept it.
HLO = f"""HloModule jit_run, is_scheduled=true

%fused_computation.1 (param_0.1: f32[8]) -> f32[8] {{
  %param_0.1 = f32[8]{{0}} parameter(0)
  %multiply.1 = f32[8]{{0}} multiply(%param_0.1, %param_0.1){_meta(LOOP + "/train/cohort/mul")}
  %add.1 = f32[8]{{0}} add(%multiply.1, %param_0.1){_meta(LOOP + "/train/sgd/add")}
  ROOT %add.2 = f32[8]{{0}} add(%add.1, %param_0.1){_meta(LOOP + "/train/cohort/add")}
}}

%fused_computation.2 (param_0.2: f32[8], param_1.2: s32[]) -> f32[8] {{
  %param_0.2 = f32[8]{{0}} parameter(0)
  %param_1.2 = s32[] parameter(1)
  %constant.9 = f32[1]{{0}} constant({{0}})
  ROOT %dynamic-update-slice.9 = f32[8]{{0}} dynamic-update-slice(%param_0.2, %constant.9, %param_1.2)
}}

%fused_computation.3 (param_0.3: f32[8]) -> f32[8] {{
  %param_0.3 = f32[8]{{0}} parameter(0)
  ROOT %maximum.3 = f32[8]{{0}} maximum(%param_0.3, %param_0.3){_meta(LOOP)}
}}

%wide.body.1 (wide.param.1: (s32[], f32[8])) -> (s32[], f32[8]) {{
  %wide.param.1 = (s32[], f32[8]{{0:T(128)}}) parameter(0)
  %get-tuple-element.1 = s32[] get-tuple-element((s32[], f32[8]{{0:T(128)}}) %wide.param.1), index=0
  %get-tuple-element.2 = f32[8]{{0:T(128)}} get-tuple-element((s32[], f32[8]{{0:T(128)}}) %wide.param.1), index=1
  %dynamic-update-slice_fusion.1 = f32[8]{{0:T(128)}} fusion(f32[8]{{0:T(128)}} %get-tuple-element.2, s32[] %get-tuple-element.1), kind=kLoop, calls=%fused_computation.2
  %copy.7 = f32[8]{{0:T(128)}} copy(f32[8]{{0:T(128)}} %get-tuple-element.2)
  ROOT %tuple.1 = (s32[], f32[8]{{0:T(128)}}) tuple(s32[] %get-tuple-element.1, f32[8]{{0:T(128)}} %dynamic-update-slice_fusion.1)
}}

%wide.cond.1 (wide.param.2: (s32[], f32[8])) -> pred[] {{
  %wide.param.2 = (s32[], f32[8]{{0:T(128)}}) parameter(0)
  ROOT %constant.2 = pred[] constant(false)
}}

%body.2 (param.3: (f32[8])) -> (f32[8]) {{
  ROOT %param.3 = (f32[8]{{0}}) parameter(0)
}}

ENTRY %main.9 (p0: f32[8], p1: f32[8]) -> f32[8] {{
  %p0 = f32[8]{{0}} parameter(0){_meta("bundles.x")}
  %p1 = f32[8]{{0}} parameter(1)
  %constant.1 = s32[] constant(0){_meta(LOOP + "/schedule/pdd/add")}
  %fusion.1 = f32[8]{{0:T(128)}} fusion(f32[8]{{0}} %p0), kind=kLoop, calls=%fused_computation.1
  %copy.1 = f32[8]{{0:T(128)}} copy(f32[8]{{0:T(128)}} %fusion.1)
  %copy-start.2 = (f32[8]{{0}}, f32[8]{{0}}, u32[]) copy-start(f32[8]{{0}} %p0)
  %copy-done.2 = f32[8]{{0}} copy-done((f32[8]{{0}}, f32[8]{{0}}, u32[]) %copy-start.2)
  %negate.1 = f32[8]{{0}} negate(f32[8]{{0}} %copy-done.2){_meta(LOOP + "/train/agg/neg")}
  %copy.3 = f32[8]{{0}} copy(f32[8]{{0}} %p1)
  %copy.4 = s32[] copy(s32[] %constant.1)
  %tuple.2 = (s32[], f32[8]{{0:T(128)}}) tuple(s32[] %copy.4, f32[8]{{0:T(128)}} %copy.1)
  %while.1 = (s32[], f32[8]{{0:T(128)}}) while((s32[], f32[8]{{0:T(128)}}) %tuple.2), condition=%wide.cond.1, body=%wide.body.1{_meta(LOOP + "/train/cohort/gather")}
  %get-tuple-element.3 = f32[8]{{0:T(128)}} get-tuple-element((s32[], f32[8]{{0:T(128)}}) %while.1), index=1
  %fusion.3 = f32[8]{{0}} fusion(f32[8]{{0:T(128)}} %get-tuple-element.3), kind=kLoop, calls=%fused_computation.3
  %copy.6 = f32[8]{{0}} copy(f32[8]{{0}} %p1)
  %tuple.3 = (f32[8]{{0}}) tuple(f32[8]{{0}} %copy.6)
  %while.2 = (f32[8]{{0}}) while((f32[8]{{0}}) %tuple.3), condition=%wide.cond.1, body=%body.2{_meta("jit(run)/round_loop/while")}
  %get-tuple-element.5 = f32[8]{{0}} get-tuple-element((f32[8]{{0}}) %while.2), index=0
  %negate.5 = f32[8]{{0}} negate(f32[8]{{0}} %get-tuple-element.5){_meta(LOOP + "/eval/neg")}
  ROOT %add.3 = f32[8]{{0}} add(f32[8]{{0}} %fusion.3, f32[8]{{0}} %negate.1){_meta(LOOP + "/eval/add")}
}}
"""


def _placed(name):
    scope = parts.fallback_scopes(HLO).get(name)
    return parts.part_of(scope) if scope else None


def test_fallback_places_a_fusion_by_its_fused_computation():
    # two of the three scoped instructions of the body are cohort's
    assert _placed("fusion.1") == "train/cohort"
    assert "multiply.1" not in parts.fallback_scopes(HLO)   # its own
    assert tracing.hlo_scopes(HLO)["negate.1"].endswith("/train/agg/neg")


def test_fallback_places_a_copy_by_its_operand_then_its_user():
    # by the fusion that produced its operand
    assert _placed("copy.1") == "train/cohort"
    # the operand is a parameter, whose op_name is the argument's name:
    # by the op that uses it
    assert _placed("copy-start.2") == "train/agg"
    assert _placed("copy-done.2") == "train/agg"
    # a constant lends no scope: by the loop that uses the copy
    assert _placed("copy.4") == "train/cohort"
    # neither an operand nor a user with a scope: left unplaced
    assert _placed("copy.3") is None


def test_fallback_crosses_into_loops_and_prefers_a_stage():
    # inside a loop body XLA made of a gather: by the loop op, reached
    # from the body's parameter or from its root
    assert _placed("dynamic-update-slice_fusion.1") == "train/cohort"
    assert _placed("copy.7") == "train/cohort"
    # its fused computation says only "the round's body": a stage found
    # through its operand wins
    assert _placed("fusion.3") == "train/cohort"
    # only the round loop takes it: a loop op is not searched past, since
    # its other results carry other data
    assert _placed("copy.6") == "other/round_loop"


def _op(name, start, dur, scope=None):
    return {"name": name, "start": float(start), "dur": float(dur),
            "scope": scope or name}


def test_reduce_parts_uses_the_fallback_only_where_metadata_is_missing():
    scopes = tracing.hlo_scopes(HLO)
    ops = [_op(n, 100 * i, 50, scopes.get(n))
           for i, n in enumerate(["fusion.1", "copy.1", "copy-start.2",
                                  "negate.1", "copy.3", "add.3"])]
    events = {"devices": {"/device:TPU:0": ops}, "host": []}
    red = parts.reduce_parts(events, parts.fallback_scopes(HLO))
    assert red["part_s"] == pytest.approx({
        "train/cohort": 100e-9, "train/agg": 100e-9, "other/rest": 50e-9,
        "eval/rest": 50e-9})
    assert red["device_ops"][0][0].startswith("train/")
    # without the fallback the metadata-less ops stay unplaced, as in
    # the per-stage reduction
    bare = parts.reduce_parts(events)["part_s"]
    assert bare == pytest.approx({"other/rest": 200e-9,
                                  "train/agg": 50e-9, "eval/rest": 50e-9})
    assert tracing.reduce_events(events)["stage_s"]["other"] == \
        pytest.approx(200e-9)


def test_recorded_trace_parts_sum_to_busy_and_refine_the_stages():
    events = _fixture()
    red = tracing.reduce_events(events)
    part_s = parts.reduce_parts(events)["part_s"]
    assert sum(part_s.values()) == pytest.approx(red["busy_s"], rel=0.01)
    for stage, t in red["stage_s"].items():
        mine = sum(v for k, v in part_s.items()
                   if k.split("/")[0] == stage)
        assert mine == pytest.approx(t, rel=1e-12), stage


# ---------------------------------------------------------------------------
# Readers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("metric,key", [
    ("cohort_ms", "train/cohort"), ("sgd_ms", "train/sgd"),
    ("agg_ms", "train/agg"), ("pdd_ms", "schedule/pdd"),
    ("unscoped_ms", "other/rest")])
def test_part_readers(metric, key):
    read = run.load_reader(metric)
    ctx = {"unit": "rounds", "units": 4, "part_s": {key: 0.002}}
    assert read(ctx) == pytest.approx(0.5)
    assert read(dict(ctx, part_s={})) == 0.0
    assert read({"unit": "rounds", "units": 4}) is None      # no parts
    assert read(dict(ctx, unit="steps")) is None


def test_a_part_the_program_adds_is_reduced_by_its_name(monkeypatch):
    from repro.telemetry import spans
    monkeypatch.setattr(spans, "PARTS", spans.PARTS + ("moe",))
    scope = f"{LOOP}/train/sgd/moe/dot_general"
    assert parts.part_of(scope) == "train/moe"
    events = {"devices": {"/device:TPU:0": [
        _op("fusion.1", 0, 30, scope),
        _op("fusion.2", 30, 20, f"{LOOP}/train/sgd/dot_general")]},
        "host": []}
    assert parts.reduce_parts(events)["part_s"] == pytest.approx(
        {"train/moe": 30e-9, "train/sgd": 20e-9})


def test_sweeps_reader():
    read = run.load_reader("assoc_sweeps")
    assert read({"assoc_sweeps": 3.25}) == 3.25
    assert read({}) is None


# ---------------------------------------------------------------------------
# The resolver's counter, after a window
# ---------------------------------------------------------------------------

def test_counter_after_a_window_reads_the_telemetry_program(monkeypatch):
    """At a small size on the CPU: the window compiles nothing, the counter
    call (a program of its own) comes after it, and it reads what a
    telemetry-on ``run_scanned`` from the window's last state reads."""
    import jax
    from repro.core import engine
    monkeypatch.setattr(run, "enable_cache", lambda: None)
    plan = plan_of("xdev1024-dense")
    plan["config"] = dict(plan["config"], n_clients=96, n_edges=8)
    counter = run.CompileCounter()
    with jax.default_matmul_precision(plan["config"]["matmul_precision"]):
        cell = run.build(plan, 2 ** 32 + 91)
        cell.setup()
        before = counter.compiles
        run.window(cell, 0.2)
        assert counter.compiles == before          # nothing in the window
        state = cell.state
        got = cell.counters()["assoc_sweeps"]
        assert counter.compiles > before           # its own program
        spec = dataclasses.replace(cell.spec, telemetry=True)
        w = cell.world
        bundle = engine.RoundBundle(dist=w.dist, x=w.x, y=w.y,
                                    counts=w.counts, test_x=w.test_x,
                                    test_y=w.test_y)
        _, out = engine.run_scanned(cell.cfg, spec, state, bundle,
                                    cell.rounds, w.actor)
    _, trace = engine.split_output(spec, out)
    sweeps = np.asarray(trace.assoc_sweeps)
    assert sweeps.shape == (cell.rounds,) and np.all(sweeps >= 1)
    assert got == float(np.mean(sweeps))


# ---------------------------------------------------------------------------
# A traced run, parts and counter in the readers' context
# ---------------------------------------------------------------------------

PART_METRICS = ("cohort_ms", "sgd_ms", "agg_ms", "pdd_ms", "unscoped_ms",
                "assoc_sweeps")
XLA_OP = re.compile(r"[\w.\-]+")


def _cpu_events(trace_dir, scopes=None):
    """``tracing.load_events`` for the CPU, whose XLA ops run on the host's
    own ``tf_XLA*`` threads: each thread's op events (named by their
    instruction) as the ops of a device, and the harness's spans."""
    import glob
    import os
    from jax.profiler import ProfileData
    scopes = scopes or {}
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)[-1]
    devices, host = {}, []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                ev_ = {"name": ev.name, "start": float(ev.start_ns),
                       "dur": float(ev.duration_ns)}
                if ev.name.startswith(tracing.HOST_PREFIX):
                    host.append(ev_)
                elif line.name.startswith("tf_XLA") \
                        and XLA_OP.fullmatch(ev.name):
                    devices.setdefault(line.name, []).append(
                        dict(ev_, scope=scopes.get(ev.name, ev.name)))
    return {"devices": devices, "host": host}


@pytest.fixture(scope="module")
def traced():
    """One traced run of the xdev cell at a small size on the CPU, with
    what the harness handed the readers."""
    import jax
    from bench import flops
    mp = pytest.MonkeyPatch()
    seen = {}
    per_layer = run.per_layer

    def keep(plan, cell, red, counted, *rest):
        seen.update(red=red, counted=counted)
        return per_layer(plan, cell, red, counted, *rest)

    v5e = flops.peaks("TPU v5 lite")
    try:
        mp.setattr(run, "enable_cache", lambda: None)
        mp.setattr(run, "per_layer", keep)
        mp.setattr(tracing, "load_events", _cpu_events)
        mp.setattr(flops, "peaks", lambda kind: v5e)
        plan = plan_of("xdev1024-dense")
        plan["config"] = dict(plan["config"], n_clients=96, n_edges=8)
        plan["traffic"] = dict(plan["traffic"], trace_seconds=0.5)
        plan["traffic"]["limits"] = dict(plan["traffic"]["limits"],
                                         model_elem_gap=0.00025)
        with jax.default_matmul_precision("highest"):
            result, info = run._run(plan, 2 ** 32 + 93, 0.5, True, False,
                                    "program")
    finally:
        mp.undo()
    return dict(seen, result=result, info=info)


def test_traced_run_parts_sum_to_busy(traced):
    red = traced["red"]
    assert red["busy_s"] > 0.0
    assert sum(red["part_s"].values()) == pytest.approx(red["busy_s"],
                                                        rel=0.01)
    assert red["part_s"]["train/sgd"] > 0.0
    # the breakdown stays keyed by stage
    for key, _ in traced["result"]["breakdown"]["device_ops"]:
        assert key.split("/")[0] in tracing.STAGES + ("other",), key


def test_traced_run_reads_parts_and_counter(traced):
    result = traced["result"]
    assert result["correct"], result["checks"]
    assert traced["info"]["in_window"] == 0
    for name in PART_METRICS:
        value = result["metrics"][name]["value"]
        assert isinstance(value, float) and value >= 0.0, name
    assert result["metrics"]["assoc_sweeps"]["value"] >= 1.0
    assert traced["counted"] == {"assoc_sweeps":
                                 result["metrics"]["assoc_sweeps"]["value"]}
