"""Each configuration of BENCHMARK.json is its own deployment: two that
share a source and the same cuts would measure one thing twice."""
from bench.harness import cell

from .conftest import ROOT

BENCH = cell.benchmark(ROOT)


def test_no_two_configs_share_source_and_cuts():
    seen = [(c["source"], tuple(sorted(c["reduced"]))) for c in BENCH["configs"]]
    assert len(seen) == len(set(seen))
