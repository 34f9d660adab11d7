"""Reduction from a JAX profiler trace (``.xplane.pb``) to device metrics.

A TPU trace holds one plane per chip (``/device:TPU:<n>``) with an
``XLA Modules`` line (one event per program run, named
``<jit name>(<fingerprint>)``) and an ``XLA Ops`` line (one event per HLO
op, named by its HLO text; a Mosaic kernel is a ``custom-call`` with
``custom_call_target="tpu_custom_call"``), and host planes whose lines
carry the benchmark's own ``jax.profiler.TraceAnnotation`` spans.  Device
and host events share one clock, to within about a millisecond.

Everything here is plain arithmetic over those events: the union of busy
intervals, time by op and by program, kernel time of one program, idle
time by the host span open over it, and the roofline share.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'
_OP = re.compile(r"^%?(?P<name>\S+) = .*? (?P<opcode>[a-z][\w\-]*)\(")


@dataclass(frozen=True)
class DeviceOp:
    chip: int
    program: str        # jit name of the program the op ran in
    name: str           # HLO instruction name, e.g. ``pad.2``
    opcode: str         # HLO opcode, e.g. ``pad``, ``custom-call``
    kernel: bool        # a Mosaic kernel (``tpu_custom_call``)
    start_ns: float
    end_ns: float


@dataclass(frozen=True)
class HostSpan:
    name: str
    start_ns: float
    end_ns: float


@dataclass
class Trace:
    ops: list           # DeviceOp, sorted by start
    runs: list          # (chip, program, start_ns, end_ns) per program run
    spans: list         # HostSpan with the annotation prefix
    chips: int


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def program_name(module_event_name: str) -> str:
    """``jit_bench_ring(1473...)`` -> ``jit_bench_ring``."""
    return module_event_name.split("(", 1)[0]


def parse_op(text: str) -> tuple:
    """(instruction name, opcode, is a Mosaic kernel) of an op event."""
    m = _OP.match(text)
    if m is None:
        return text.split(" ", 1)[0].lstrip("%"), "", False
    return m["name"], m["opcode"], KERNEL_TARGET in text


def load(path: str, span_prefix: str = "bench.") -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, runs, spans, chips = [], [], [], 0
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            chip = int(plane.name[len(DEVICE_PLANE):])
            chips += 1
            lines = {line.name: list(line.events) for line in plane.lines}
            modules = sorted((e.start_ns, e.end_ns, program_name(e.name))
                             for e in lines.get(MODULES_LINE, ()))
            runs += [(chip, p, s, e) for s, e, p in modules]
            m = 0
            for e in sorted(lines.get(OPS_LINE, ()), key=lambda e: e.start_ns):
                while m < len(modules) and modules[m][1] < e.start_ns:
                    m += 1
                inside = m < len(modules) and modules[m][0] <= e.start_ns
                name, opcode, kernel = parse_op(e.name)
                ops.append(DeviceOp(chip, modules[m][2] if inside else "",
                                    name, opcode, kernel,
                                    e.start_ns, e.end_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(span_prefix):
                        spans.append(HostSpan(e.name, e.start_ns, e.end_ns))
    ops.sort(key=lambda o: o.start_ns)
    spans.sort(key=lambda s: s.start_ns)
    return Trace(ops, runs, spans, chips)


def _clip(ops, lo, hi):
    for o in ops:
        s, e = max(o.start_ns, lo), min(o.end_ns, hi)
        if e > s:
            yield o, s, e


def union_ns(intervals) -> float:
    """Total length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_ns(trace: Trace, lo: float, hi: float) -> float:
    """Union of op intervals inside [lo, hi], averaged over the chips."""
    if not trace.chips:
        return 0.0
    per_chip = {}
    for o, s, e in _clip(trace.ops, lo, hi):
        per_chip.setdefault(o.chip, []).append((s, e))
    return sum(union_ns(v) for v in per_chip.values()) / trace.chips


def time_by_op(trace: Trace, lo: float, hi: float) -> dict:
    """Device ns per ``<program>/<op>`` inside [lo, hi], summed over chips
    (a kernel is marked ``[kernel]``)."""
    out: dict = {}
    for o, s, e in _clip(trace.ops, lo, hi):
        key = f"{o.program}/{o.name}" + (" [kernel]" if o.kernel else "")
        out[key] = out.get(key, 0.0) + (e - s)
    return out


def kernel_ns(trace: Trace, program: str) -> tuple:
    """(device ns of the Mosaic kernels of one program, runs of that
    program), over the whole trace and summed over chips.  Runs are not
    clipped to a host span: the device's clock may read a millisecond
    early, and the profiler is on only around the window anyway."""
    ns = sum(o.end_ns - o.start_ns for o in trace.ops
             if o.program == program and o.kernel)
    return ns, sum(1 for _, p, _, _ in trace.runs if p == program)


def program_ns(trace: Trace, program: str) -> tuple:
    """(device ns, runs) of every run of one program in the trace, summed
    over chips: the whole program, kernels and the XLA ops around them."""
    runs = [(s, e) for _, p, s, e in trace.runs if p == program]
    return sum(e - s for s, e in runs), len(runs)


def idle_gaps(trace: Trace, lo: float, hi: float, top: int = 10) -> list:
    """Idle time inside [lo, hi] (no op running on any chip) by what the
    host was doing: each stretch of idle time is charged to the innermost
    host span open over it (``host:none`` where none was), and the
    ``top`` names with the most idle seconds come first."""
    busy = sorted((s, e) for _, s, e in _clip(trace.ops, lo, hi))
    gaps, cur = [], lo
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        gaps.append((cur, hi))
    spans = [sp for sp in trace.spans if sp.end_ns > lo and sp.start_ns < hi]
    by_name: dict = {}
    first = 0
    for s, e in gaps:
        while first < len(spans) and spans[first].end_ns <= s:
            first += 1
        cuts = {s, e}
        open_ = []
        for sp in spans[first:]:
            if sp.start_ns >= e:
                break
            if sp.end_ns > s:
                open_.append(sp)
                cuts.update(t for t in (sp.start_ns, sp.end_ns) if s < t < e)
        cuts = sorted(cuts)
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            around = [sp for sp in open_ if sp.start_ns <= mid < sp.end_ns]
            name = (max(around, key=lambda sp: sp.start_ns).name if around
                    else "host:none")
            by_name[name] = by_name.get(name, 0.0) + (b - a) * 1e-9
    return sorted(by_name.items(), key=lambda kv: -kv[1])[:top]


def span_bounds(trace: Trace, name: str) -> tuple:
    """(start, end) ns of the one host span called ``name``."""
    found = [sp for sp in trace.spans if sp.name == name]
    if len(found) != 1:
        raise ValueError(f"expected one host span {name!r}, found "
                         f"{len(found)}")
    return found[0].start_ns, found[0].end_ns


def roofline(flops: float, nbytes: float, seconds: float,
             peaks: dict) -> tuple:
    """(share in %, bound) of a call that needs ``flops`` and ``nbytes``
    and took ``seconds``: the least time the chip could take over the time
    taken, bound by ``compute`` or ``HBM``, whichever is larger."""
    t_compute = flops / peaks["flops_bf16"]
    t_hbm = nbytes / peaks["hbm_bytes_per_s"]
    least = max(t_compute, t_hbm)
    return 100.0 * least / seconds, ("compute" if t_compute >= t_hbm
                                     else "HBM")
