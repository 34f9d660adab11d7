"""pick_roofline: the pick's Mosaic kernel time per call in the traced
window against the least time the chip could take for the work the
configuration needs (its family's work function, the peaks table)."""
from bench.harness import trace as tr


def read(run):
    traced, cand = run.record.get("trace"), run.record.get("candidates")
    if traced is None or not cand:
        return None
    program = cand[run.record["pick"]]["program"]
    ns, runs = tr.kernel_ns(traced["trace"], program)
    if not ns or not runs:
        return None
    need = run.record["work"]
    share, bound = tr.roofline(need["flops"], need["bytes"],
                               ns * 1e-9 / runs, run.peaks)
    return share, (f"{program}: {runs} runs, kernel {ns * 1e-6 / runs!r} ms "
                   f"per run, bound by {bound}")
