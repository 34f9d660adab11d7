"""What decides ``correct``: the program's outputs pass, the control (the
plain reference in the next lower precision, put in the program's place)
fails, and each fault of the timed path a cell can have makes ``correct``
false.  Every test runs over the cells of BENCHMARK.json, so a cell added
there is tested here with no edit.  The harness runs end to end on the CPU
at tiny sizes, with its look for a chip skipped and kernels in interpret
mode."""
import pytest

from bench.harness import cell, runner

from .conftest import BENCH, cells, tiny_cell, tiny_config


def _wrap_entry(monkeypatch, name, change):
    """Make every candidate's output of cell ``name`` go through
    ``change(out, inputs)``."""
    program = cell.family(tiny_cell(name)["config"])[0]
    entry = program.entry

    def broken(shape, cfg):
        fn = entry(shape, cfg)

        def run(*args):
            return change(fn(*args), args)

        run.__name__ = fn.__name__
        return run

    monkeypatch.setattr(program, "entry", broken)


@pytest.mark.parametrize("name", cells())
def test_the_program_is_correct(cpu_harness, name):
    out, _ = cpu_harness(name)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0


@pytest.mark.parametrize("name", cells())
def test_the_control_in_the_programs_place_is_not(cpu_harness, monkeypatch,
                                                  name):
    def mosaic_only(fn, what):     # as on the chip: the control is plain XLA
        raise RuntimeError(f"{what}: not compiled as a TPU kernel")

    monkeypatch.setattr(runner.device, "check_compiled", mosaic_only)
    out, _ = cpu_harness(name, control=True)
    assert out["correct"] is False
    errors = {k: v for k, v in out["checks"].items()
              if k.startswith("max_abs_error.")}
    assert errors and all(v["value"] > v["limit"] for v in errors.values())


@pytest.mark.parametrize("name", cells())
def test_an_answer_altered_where_it_is_produced(cpu_harness, monkeypatch,
                                                name):
    _wrap_entry(monkeypatch, name,
                lambda out, args: out.at[(0,) * out.ndim].add(1.0))
    out, _ = cpu_harness(name)
    assert out["correct"] is False


@pytest.mark.parametrize("name", cells())
def test_half_of_the_batch_left_out(cpu_harness, monkeypatch, name):
    import jax.numpy as jnp

    def half(out, args):
        keep = out.shape[0] // 2
        return jnp.concatenate([out[:keep], jnp.zeros_like(out[keep:])])

    _wrap_entry(monkeypatch, name, half)
    out, _ = cpu_harness(name)
    assert out["correct"] is False


@pytest.mark.parametrize("name", cells("sweep"))
@pytest.mark.parametrize("fault", ["compile", "not_mosaic"])
def test_a_candidate_refused_by_the_compiler(cpu_harness, monkeypatch, name,
                                             fault):
    """A candidate other than the pick that does not compile, or compiles
    to something that is not a Mosaic kernel, makes the run not correct:
    it cannot leave the set that regret is taken over unseen."""
    seen = []

    def refuse_after_the_pick(what):
        seen.append(what)
        if len(seen) == 2:      # the served pick is compiled first
            raise RuntimeError(f"{what}: refused")

    if fault == "compile":
        _wrap_entry(monkeypatch, name,
                    lambda out, args: refuse_after_the_pick("trace") or out)
    else:
        monkeypatch.setattr(runner.device, "check_compiled",
                            lambda fn, what: refuse_after_the_pick(what))
    out, run = cpu_harness(name)
    assert out["correct"] is False
    assert out["checks"]["refused"] == {"value": 1, "limit": 0}
    assert out["failed"] == 1
    assert out["attempted"] == len(run.record["candidates"]) + 1


@pytest.mark.parametrize("name", cells("closed_cold"))
@pytest.mark.parametrize("fault", ["drop", "retime"])
def test_a_served_ranking_altered(cpu_harness, monkeypatch, name, fault):
    price = runner.Run.price
    calls = []

    def altered(self, cands, label):
        ranking, skipped = price(self, cands, label)
        calls.append(label)
        if len(calls) > 1:              # requests of the window, not set-up
            if fault == "drop":
                ranking = ranking[:-1]
            else:
                ranking = [(c, t * 1.001) for c, t in ranking]
        return ranking, skipped

    monkeypatch.setattr(runner.Run, "price", altered)
    out, _ = cpu_harness(name)
    assert out["correct"] is False
    assert out["failed"] > 0 or out["checks"]["library_mismatch"]["value"]


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_limits_lie_between_program_and_control(name):
    """At a tiny size on the CPU, the configuration's limit separates the
    program's error from the control's, as the chip's readings do at the
    cell's size (PERF.md gives those)."""
    import os

    import jax

    from .conftest import ROOT

    entry = {c["name"]: c for c in BENCH["configs"]}[name]
    config = tiny_config(cell.load_json(os.path.join(ROOT, entry["file"])))
    program, ref, _ = cell.family(config)
    shape = program.shape(config)
    limit = config["check"]["max_abs_error"]
    x = program.inputs(shape, runner.seed_key(3))
    want = ref.reference(shape, x)
    for cfg, _ in program.candidates(shape):
        got = jax.jit(program.entry(shape, cfg))(*x)
        assert runner.max_abs_error(got, want) <= limit
    assert runner.max_abs_error(ref.control(shape, x), want) > limit
