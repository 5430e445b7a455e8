"""Trace reduction: busy union, kernel time, idle gaps labelled by the
host span that covers them; checked on synthetic planes and on an excerpt
of a trace recorded on a TPU v5e."""
import gzip
import json
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import run, trace_reduce

KERNELS = run.load_arch("dense_gqa").KERNELS


def _ev(name, start, dur, stats=()):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=stats)


def _planes():
    ms = 1_000_000
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[_ev("jit_step", 0, 100 * ms)]),
        NS(name="XLA Ops", events=[
            _ev("fusion.1", 10 * ms, 20 * ms),
            _ev("paged_attention_update_pallas.5", 25 * ms, 15 * ms,
                stats=[("flops", 12)]),
            # XLA work inside the kernel's jitted wrapper, not the kernel
            _ev("fusion.75", 70 * ms, 4 * ms, stats=[
                ("long_name", "jit(step)/while/body/closed_call/"
                 "jit(paged_attention_update_pallas)/reduce_min")]),
            _ev("fusion.2", 74 * ms, 6 * ms),
            _ev("outside", 200 * ms, 10 * ms)])])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("bench.window", 0, 100 * ms),
        _ev("bench.step_begin", 40 * ms, 30 * ms),
        _ev("bench.pump", 45 * ms, 5 * ms),
        _ev("other", 0, 100 * ms)])])
    return [host, device]


def test_busy_is_the_union_inside_the_window():
    r = trace_reduce.reduce_planes(_planes(), KERNELS)
    assert r["window_s"] == pytest.approx(0.1)
    # [10, 40) and [70, 80): 40 ms; the op at 200 ms is outside
    assert r["busy_s"] == pytest.approx(0.040)
    assert r["kernel_s"]["paged_attention"] == pytest.approx(0.015)
    names = [n for n, _ in r["top_ops"]]
    assert names[0] == "fusion.1" and "outside" not in names


def test_idle_gaps_are_labelled_by_the_innermost_host_span():
    r = trace_reduce.reduce_planes(_planes(), KERNELS)
    gaps = dict((round(s, 6), n) for n, s in r["idle_gaps"])
    # [40, 70) has its middle (55) in bench.step_begin, not in pump
    assert gaps[0.03] == "bench.step_begin"
    # [80, 100) and [0, 10) are covered by no bench span
    assert gaps[0.02] == "host:other" and gaps[0.01] == "host:other"
    assert sum(s for _, s in r["idle_gaps"]) == pytest.approx(0.06)


def test_kernels_are_the_table_given():
    """The running configuration's module names its kernels: the same
    planes read another table's kernel, or none."""
    ms = 1_000_000
    r = trace_reduce.reduce_planes(_planes(), {"mlp": ("fusion",)})
    # fusion.1 and fusion.2; fusion.75 sits inside the kernel's wrapper
    # but is a fusion of its own name, so it counts too
    assert r["kernel_s"] == pytest.approx({"mlp": 0.030})
    assert trace_reduce.reduce_planes(_planes(), {})["kernel_s"] == {}
    assert r["busy_s"] == trace_reduce.reduce_planes(
        _planes(), KERNELS)["busy_s"] == pytest.approx(40 * ms * 1e-9)


def test_idle_gaps_are_named_by_the_programs_phases():
    ms = 1_000_000
    planes = _planes()
    planes[0].lines.append(NS(name="python-2", events=[
        _ev("serve.sync", 41 * ms, 20 * ms),
        _ev("serve.gc", 80 * ms, 15 * ms)]))
    gaps = dict((round(s, 6), n) for n, s in
                trace_reduce.reduce_planes(planes, KERNELS)["idle_gaps"])
    # [40, 70): middle 55 in serve.sync, inside bench.step_begin
    assert gaps[0.03] == "serve.sync"
    # [80, 100): middle 90 in serve.gc; [0, 10) under no span
    assert gaps[0.02] == "serve.gc" and gaps[0.01] == "host:other"


def test_no_device_plane_reads_nothing():
    host = NS(name="/host:CPU", lines=[])
    r = trace_reduce.reduce_planes([host], KERNELS)
    assert r["busy_s"] == 0.0 and r["kernel_s"] == {}



# two ticks of a traced glm4-9b-20l.prefill window on a TPU v5e (20
# layers): one decode-only, one carrying a prefill chunk; ops are named by
# their HLO text, and each tick's layer loop encloses its body's ops
RECORDED = Path(__file__).parent / "data" / "glm4_ticks.trace.json.gz"


def _recorded():
    doc = json.loads(gzip.decompress(RECORDED.read_bytes()))
    names = doc["names"]
    return [NS(name=p["name"], lines=[
        NS(name=ln["name"], events=[_ev(names[i], s, d)
                                    for i, s, d in ln["events"]])
        for ln in p["lines"]]) for p in doc["planes"]]


def _ops(planes):
    return [e for p in planes if p.name.startswith("/device:")
            for ln in p.lines if ln.name == trace_reduce.OPS_LINE
            for e in ln.events]


def test_recorded_trace_kernel_is_its_custom_call_alone():
    planes = _recorded()
    ops = _ops(planes)
    calls = [e for e in ops if " custom-call(" in e.name]
    assert len(calls) == 2 * 20 and all(
        trace_reduce.kernel_of(e.name, KERNELS) == "paged_attention"
        for e in calls)
    assert sum(1 for e in ops if trace_reduce.kernel_of(e.name, KERNELS)) \
        == 40
    r = trace_reduce.reduce_planes(planes, KERNELS)
    assert r["kernel_s"]["paged_attention"] == pytest.approx(
        sum(e.duration_ns for e in calls) * 1e-9)


def test_recorded_trace_ops_count_their_own_time():
    planes = _recorded()
    ops = _ops(planes)
    r = trace_reduce.reduce_planes(planes, KERNELS)
    loops = [e for e in ops if trace_reduce.op_name(e.name).startswith(
        "while.")]
    assert loops
    # a layer loop encloses nearly all of its tick: counted whole, the
    # ops would add up to about twice the busy time
    assert sum(e.duration_ns for e in ops) * 1e-9 > 1.5 * r["busy_s"]
    assert sum(s for _, s in r["top_ops"]) <= r["busy_s"] * (1 + 1e-9)
    top, secs = r["top_ops"][0]
    assert trace_reduce.kernel_of(top, KERNELS) == "paged_attention"
    # the kernel encloses no op: its own time is its whole time
    assert secs == pytest.approx(sum(
        e.duration_ns for e in ops
        if e.name[:trace_reduce.SHOWN] == top) * 1e-9)
    assert all(len(n) <= trace_reduce.SHOWN for n, _ in r["top_ops"])
