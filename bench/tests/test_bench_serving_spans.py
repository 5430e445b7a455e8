"""The program's serving spans and tick counters as the benchmark reads
them: ``host_gap_ms`` and ``frontend_ms_per_tick`` on synthetic runs and
on a tiny traced run, and the accepted trace reduction reading as before
beside the program's ``serve.*`` host spans."""
import gzip
import json
import math
import time
from types import SimpleNamespace as NS

import pytest

from bench import run, trace_reduce, traffic
from bench import window as win

DATA = run.BENCH / "tests" / "data"


def _reader(name):
    return run.load_reader(name)


def _run_data(ticks):
    return win.RunData(
        model={}, mix={}, engine={}, t_open=10.0, t_close=20.0, ticks=[],
        telemetry_ticks=ticks, telemetry_spans=[], telemetry_epoch=10.0,
        window={}, reached_t={}, submit_t={}, compiles_in_window=0,
        trace=None, peak=None)


def _tick(i, t, dispatch_t, ready_t, **phase_s):
    return {"tick": i, "t": t, "dispatch_t": dispatch_t,
            "ready_t": ready_t, "phase_s": phase_s}


def test_host_gap_is_next_dispatch_less_ready():
    ticks = [_tick(0, 0.0, 0.001, 0.100),
             _tick(1, 0.100, 0.103, 0.200),
             _tick(2, 0.200, 0.205, 0.300),
             # tick 3 fell off the ring: no gap across the hole
             _tick(4, 0.400, 0.500, 0.600),
             _tick(5, 11.0, 11.1, 11.2)]     # after the window
    assert _reader("host_gap_ms").read(_run_data(ticks)) == \
        pytest.approx(4.0)


def test_frontend_time_per_tick():
    ticks = [_tick(0, 1.0, None, None, pump=1e-4, route=2e-4, sync=0.1),
             _tick(1, 2.0, None, None, harvest=3e-4, pack=0.2)]
    assert _reader("frontend_ms_per_tick").read(_run_data(ticks)) == \
        pytest.approx(0.3)


def test_readers_read_nothing_without_the_program_fields():
    # tick events of a program before schema 5 carry neither field
    old = [{"tick": i, "t": float(i)} for i in range(3)]
    for name in ("host_gap_ms", "frontend_ms_per_tick"):
        assert _reader(name).read(_run_data(old)) is None
        assert _reader(name).read(_run_data([])) is None


def test_tiny_traced_run_reports_the_program_spans():
    per_layer = [{"name": m["name"], "unit": m["unit"]}
                 for m in run.load_manifest()["per_layer"]]
    man = {"end_to_end": [], "per_layer": per_layer}
    cell = {"name": "tiny.tiny", "config": "tiny", "traffic": "tiny",
            "chips": 1}
    res = run.run_cell(
        man, cell, 2 ** 31 + 7, 1.0, True,
        spec=run.load_config("tiny", DATA),
        mix=traffic.load_mix("tiny", DATA / "mixes"),
        limits=run.load_limits("tiny", DATA), t_process=time.perf_counter(),
        peak={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    assert res["correct"], res["checks"]
    m = res["metrics"]
    for name in ("host_gap_ms", "frontend_ms_per_tick"):
        assert m[name]["unit"] == "ms"
        assert math.isfinite(m[name]["value"]) and m[name]["value"] > 0


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=())


def test_serve_spans_leave_the_reduction_alone():
    """Device numbers read no host span; gaps are still named by the
    harness's ``bench.*`` spans."""
    ms = 1_000_000
    device = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=[
        _ev("paged_attention_update_pallas.5", 10 * ms, 20 * ms),
        _ev("fusion.2", 60 * ms, 30 * ms)])])

    def host(*serve):
        return NS(name="/host:CPU", lines=[NS(name="python3", events=[
            _ev("bench.window", 0, 100 * ms),
            _ev("bench.step_end", 30 * ms, 25 * ms), *serve])])

    plain = trace_reduce.reduce_planes([host(), device])
    spans = trace_reduce.reduce_planes([host(
        _ev("serve.sync", 30 * ms, 5 * ms),
        _ev("serve.unpack", 35 * ms, 15 * ms),
        _ev("serve.pack", 55 * ms, 4 * ms)), device])
    assert spans == plain
    assert dict((round(s, 6), n) for n, s in spans["idle_gaps"])[0.03] \
        == "bench.step_end"


def _recorded(name):
    doc = json.loads(gzip.decompress((DATA / name).read_bytes()))
    names = doc["names"]
    return [NS(name=p["name"], lines=[
        NS(name=ln["name"], events=[_ev(names[i], s, d)
                                    for i, s, d in ln["events"]])
        for ln in p["lines"]]) for p in doc["planes"]]


def test_recorded_glm4_excerpt_reads_as_before():
    r = trace_reduce.reduce_planes(_recorded("glm4_ticks.trace.json.gz"))
    assert r["busy_s"] == pytest.approx(0.249264691, abs=1e-12)
    assert r["window_s"] == pytest.approx(10.179665415, abs=1e-12)
    assert r["kernel_s"] == pytest.approx({"paged_attention": 0.219313706},
                                          abs=1e-12)
    top = [(n.split(" = ")[0], round(s, 9)) for n, s in r["top_ops"]]
    assert top[:3] == [("%paged_attention_update_pallas.5", 0.189704086),
                       ("%paged_attention_update_pallas.4", 0.02960962),
                       ("%iota_reduce_fusion", 0.003284115)]
    assert len(top) == 10


def test_recorded_granite_excerpt_has_the_program_spans():
    """Two ticks of a traced ``granite-3-2b.decode`` window on a TPU v5e:
    the kernel's custom calls keep the name the reduction finds (40 a
    tick), and the device's idle gap between the ticks lies under the
    program's own host phases, the sync first."""
    planes = _recorded("granite_ticks.trace.json.gz")
    ops = [e for p in planes if p.name.startswith("/device:")
           for ln in p.lines if ln.name == trace_reduce.OPS_LINE
           for e in ln.events]
    calls = [e for e in ops if " custom-call(" in e.name
             and "tpu_custom_call" in e.name]
    assert len(calls) == 2 * 40
    assert {trace_reduce.op_name(e.name).rsplit(".", 1)[0]
            for e in calls} == {"paged_attention_update_pallas"}
    r = trace_reduce.reduce_planes(planes)
    assert r["kernel_s"]["paged_attention"] == pytest.approx(
        sum(e.duration_ns for e in calls) * 1e-9)

    busy = trace_reduce._union([(e.start_ns, e.start_ns + e.duration_ns)
                                for e in ops])
    between = [(a[1], b[0]) for a, b in zip(busy, busy[1:])
               if b[0] - a[1] > 1e6]           # longer than 1 ms
    assert len(between) == 1
    lo, hi = between[0]
    serve = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                   for p in planes if p.name.startswith("/host:")
                   for ln in p.lines for e in ln.events
                   if e.name.startswith("serve."))
    under = [n for a, b, n in serve if b > lo and a < hi]
    assert under[0] == "serve.sync" and under[-1] == "serve.dispatch"
    assert {"serve.unpack", "serve.record", "serve.route", "serve.pump",
            "serve.admit", "serve.pack"} <= set(under)
    mid = (lo + hi) / 2
    assert any(a <= mid <= b for a, b, _ in serve)
