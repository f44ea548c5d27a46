"""Every cell's traffic, driven through the whole harness at a tiny size
on the CPU (kernels in the Pallas interpreter): the answers match the
plain reference exactly."""
import json

import pytest

import bench
import tiny

CELLS = [w["name"] for w in json.loads(
    (bench.CHECKOUT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_matches_reference(name):
    out = tiny.run(name, seconds=1.0)
    assert out["correct"], out["checks"]
    assert out["checks"]["mismatches"]["value"] == 0
    assert out["checked"] > 0 and out["attempted"] > 0
    assert "setup_s" in out["metrics"]
    assert list(out)[-1] == "checks"


def test_ingest_window_counts_whole_groups():
    """The rate's tokens are a whole number of commit groups."""
    c = tiny.cell("wiki-mdbl.ingest")
    group = c.traffic["calls"] * c.traffic["tokens_per_call"]
    out = tiny.run("wiki-mdbl.ingest", seconds=1.0)
    assert out["attempted"] % group == 0


def test_same_seed_same_traffic():
    import generator
    c = tiny.cell("wiki-mdbl.ingest")
    a, b = generator.Corpus(c.cfg, 5), generator.Corpus(c.cfg, 5)
    assert (a.group(3, 4096) == b.group(3, 4096)).all()
    assert (a.preload() == b.preload()).all()
    assert not (a.group(3, 4096) == generator.Corpus(c.cfg, 6)
                .group(3, 4096)).all()
