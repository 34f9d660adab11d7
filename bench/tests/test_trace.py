"""The reduction from trace to metrics, on a trace recorded on one TPU v5
lite: one call of the stencil's ``ring`` candidate (a pad, then the
kernel) and one of flash attention's bq=1024 bk=2048 candidate, each in a
host span of its own."""
import os

import pytest

from bench.harness import trace as tr

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "v5e_stencil_flash.xplane.pb")
# device durations of the three ops, as the trace records them (ns)
PAD, RING, FLASH = 2353837.0, 4067788.0, 13887317.0


@pytest.fixture(scope="module")
def recorded():
    t = tr.load(DATA, span_prefix="visit.")
    lo = t.spans[0].start_ns - 5e6
    hi = t.spans[-1].end_ns
    return t, lo, hi


def test_planes_ops_and_spans(recorded):
    t, _, _ = recorded
    assert t.chips == 1
    assert [(o.name, o.opcode, o.kernel) for o in t.ops] == [
        ("pad.2", "pad", False), ("_apply.1", "custom-call", True),
        ("_lambda_.1", "custom-call", True)]
    assert {o.program for o in t.ops} == {"jit__lambda"}
    assert len(t.runs) == 2
    assert [s.name for s in t.spans] == ["visit.stencil.ring",
                                        "visit.flash.pick"]


def test_busy_is_the_union_of_op_intervals(recorded):
    t, lo, hi = recorded
    assert tr.busy_ns(t, lo, hi) == pytest.approx(PAD + RING + FLASH)
    # clipped to a window that ends inside the flash kernel
    flash = t.ops[2]
    cut = flash.start_ns + 1e6
    assert tr.busy_ns(t, lo, cut) == pytest.approx(PAD + RING + 1e6)


def test_union_of_overlapping_intervals():
    assert tr.union_ns([(0, 10), (5, 15), (20, 30), (30, 31)]) == 26
    assert tr.union_ns([]) == 0


def test_time_by_op_and_kernel_time(recorded):
    t, lo, hi = recorded
    by_op = tr.time_by_op(t, lo, hi)
    assert by_op == pytest.approx({
        "jit__lambda/pad.2": PAD,
        "jit__lambda/_apply.1 [kernel]": RING,
        "jit__lambda/_lambda_.1 [kernel]": FLASH})
    ns, runs = tr.kernel_ns(t, "jit__lambda")
    assert ns == pytest.approx(RING + FLASH)     # the pad is no kernel
    assert runs == 2
    assert tr.kernel_ns(t, "jit_other") == (0, 0)


def test_program_time_is_its_runs(recorded):
    t, _, _ = recorded
    ns, runs = tr.program_ns(t, "jit__lambda")
    assert runs == 2
    # each run spans its ops and the little between them
    assert PAD + RING + FLASH <= ns <= 1.01 * (PAD + RING + FLASH)
    assert tr.program_ns(t, "jit_other") == (0, 0)


def test_idle_time_is_charged_to_the_host_span_open_over_it(recorded):
    t, lo, hi = recorded
    gaps = dict(tr.idle_gaps(t, lo, hi))
    busy = tr.busy_ns(t, lo, hi)
    assert sum(gaps.values()) == pytest.approx((hi - lo - busy) * 1e-9)
    assert set(gaps) == {"host:none", "visit.stencil.ring",
                         "visit.flash.pick"}
    # before the first span: the 5 ms the window opens early, at least
    assert gaps["host:none"] >= 5e-3
    # the stencil's span closes after its ops end: its tail is idle
    ring = t.spans[0]
    last = max(o.end_ns for o in t.ops[:2])
    assert gaps["visit.stencil.ring"] >= (ring.end_ns - last) * 1e-9 - 1e-12


def test_idle_time_of_nested_spans_goes_to_the_innermost():
    op = tr.DeviceOp(0, "p", "op", "x", False, 40.0, 60.0)
    outer = tr.HostSpan("bench.window", 0.0, 100.0)
    inner = tr.HostSpan("bench.serve", 10.0, 30.0)
    t = tr.Trace([op], [], [outer, inner], 1)
    gaps = dict(tr.idle_gaps(t, 0.0, 100.0))
    assert gaps == pytest.approx({"bench.window": 60e-9,
                                  "bench.serve": 20e-9})


def test_roofline_names_its_bound():
    peaks = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    # the stencil at (512, 512, 640), r=4, f32: HBM-bound
    share, bound = tr.roofline(2 * 25 * 512 * 512 * 640,
                               4 * (520 * 520 * 648 + 512 * 512 * 640),
                               RING * 1e-9, peaks)
    assert bound == "HBM"
    assert share == pytest.approx(100 * 1.372e9 / 819e9 / (RING * 1e-9),
                                  rel=1e-3)
    share, bound = tr.roofline(197e12, 1.0, 2.0, peaks)
    assert (share, bound) == (50.0, "compute")


def test_parse_op_of_a_tuple_typed_fusion():
    text = ("%fusion.3 = (f32[8]{0}, f32[8]{0}) fusion(f32[8]{0} %p), "
            "kind=kLoop")
    assert tr.parse_op(text) == ("fusion.3", "fusion", False)
