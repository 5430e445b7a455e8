"""From a profiler trace of one window to the benchmark's device numbers.

The trace is JAX's ``.xplane.pb``, read with ``jax.profiler.ProfileData``
and nothing else.  Device planes (``/device:TPU:N``) carry one event per
operation on the ``XLA Ops`` line, named by the instruction's HLO text
(``%paged_attention_update_pallas.5 = (bf16[...]) custom-call(...)``);
a loop's event (``%while.2``) encloses the events of its body.  The host
plane carries the harness's own ``bench.*`` spans and the program's
``serve.*`` phases (``jax.profiler.TraceAnnotation``), among them
``bench.window``, which bounds the measured window on the trace's clock.

Reduced here, within the window:

- busy time: the union of a device's op intervals, averaged over the
  devices that ran anything;
- kernel time: the summed durations of each kernel's own events: the
  Pallas custom call, whose instruction is named after the kernel
  (``paged_attention_update_pallas.5``); the XLA fusions and copies
  around it (the lane-padded pool copy) have names of their own and are
  not the kernel.  Which kernels, and their names, come from the running
  configuration's architecture module (its ``KERNELS``: a kernel's name
  -> the HLO names of its custom calls, each op's name being the
  instruction's with a ``.N`` suffix);
- the operations that took most time, each by its own time: a loop's
  event less the events inside it;
- idle gaps: stretches with no op on the device, each labelled by the
  innermost ``bench.*`` or ``serve.*`` host span that covers its middle.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
HOST_SPANS = ("bench.", "serve.")     # the harness's spans, the program's

SHOWN = 160          # characters of an op's HLO text in the breakdown


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.start_ns + e.duration_ns)


def op_name(text: str) -> str:
    """The HLO instruction name of a device op's event name (its HLO text
    ``%name = ...``, or the bare name)."""
    return text.split(" = ", 1)[0].lstrip("%")


def _self_times(ops: List[Tuple[str, float, float]]) -> List[float]:
    """Each op's duration less those of the ops directly inside it."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    own = [b - a for _, a, b in ops]
    stack: List[int] = []
    for i in order:
        a, b = ops[i][1], ops[i][2]
        while stack and ops[stack[-1]][2] <= a:
            stack.pop()
        if stack and b <= ops[stack[-1]][2]:
            own[stack[-1]] -= b - a
        stack.append(i)
    return own


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def kernel_of(text: str, kernels: Dict[str, tuple]) -> Optional[str]:
    """The kernel of ``kernels`` whose custom call the device op ``text``
    is (an event's HLO text or its instruction name)."""
    name = op_name(text)
    base = name.rsplit(".", 1)
    base = base[0] if len(base) == 2 and base[1].isdigit() else name
    for kernel, names in kernels.items():
        if base in names:
            return kernel
    return None


def reduce_planes(planes, kernels: Dict[str, tuple]) -> dict:
    """``planes``: iterable of objects with ``name`` and ``lines`` (each
    line with ``name`` and ``events`` of ``name``, ``start_ns``,
    ``duration_ns`` and ``stats``) — a ``ProfileData``'s planes;
    ``kernels``: the architecture module's ``KERNELS``."""
    host_spans: List[Tuple[str, float, float]] = []
    device_ops: Dict[str, List[Tuple[str, float, float]]] = {}
    for plane in planes:
        if plane.name.startswith("/device:"):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend(_events(line))
            if ops:
                device_ops[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in _events(line):
                    if ev[0].startswith(HOST_SPANS):
                        host_spans.append(ev)
    wins = [s for s in host_spans if s[0] == WINDOW_SPAN]
    if wins:
        lo, hi = wins[0][1], wins[0][2]
    elif device_ops:
        every = [e for ops in device_ops.values() for e in ops]
        lo, hi = min(e[1] for e in every), max(e[2] for e in every)
    else:
        return {"busy_s": 0.0, "window_s": 0.0, "kernel_s": {},
                "top_ops": [], "idle_gaps": [], "devices": 0}
    window_s = (hi - lo) * 1e-9

    busy = []
    per_op: Dict[str, float] = defaultdict(float)
    kernel_s: Dict[str, float] = defaultdict(float)
    gaps: List[Tuple[float, float]] = []
    for ops in device_ops.values():
        inside = [(n, max(a, lo), min(b, hi))
                  for n, a, b in ops if b > lo and a < hi]
        merged = _union([(a, b) for _, a, b in inside])
        busy.append(sum(b - a for a, b in merged) * 1e-9)
        for (n, a, b), own in zip(inside, _self_times(inside)):
            per_op[n[:SHOWN]] += own * 1e-9
            k = kernel_of(n, kernels)
            if k:
                kernel_s[k] += (b - a) * 1e-9
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
    spans = [s for s in host_spans if s[0] != WINDOW_SPAN]

    def label(a, b):
        mid = (a + b) / 2
        cover = [s for s in spans if s[1] <= mid <= s[2]]
        if not cover:
            return "host:other"
        return min(cover, key=lambda s: s[2] - s[1])[0]

    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    named = [[label(a, b), (b - a) * 1e-9] for a, b in longest]
    n = len(device_ops)
    return {
        "busy_s": sum(busy) / n if n else 0.0,
        "window_s": window_s,
        "kernel_s": {k: v / n for k, v in kernel_s.items()},
        "top_ops": sorted(([k, v / n] for k, v in per_op.items()),
                          key=lambda kv: -kv[1])[:10],
        "idle_gaps": named,
        "devices": n,
    }


def find_trace(trace_dir: str) -> Optional[str]:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def reduce_file(path: str, kernels: Dict[str, tuple]) -> dict:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes, kernels)


def reduce_dir(trace_dir: str, kernels: Dict[str, tuple]) -> Optional[dict]:
    path = find_trace(trace_dir)
    return reduce_file(path, kernels) if path else None
