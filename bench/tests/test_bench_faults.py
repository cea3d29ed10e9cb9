"""Whole runs without the chip, at a tiny size: a sound program comes out
correct and its float8 control does not, and each fault a one-chip
serving cell can have, planted under the timed path, comes out as not
correct."""
import pytest

from bench import faults
from bench.harness import run
from conftest import TINY_LIMIT, tiny_cell

SEED = 2 ** 33 + 17


def tiny_run(cell, fault=None, control=False):
    return run(cell, SEED, 1.5, False, require_tpu=False,
               memory_bytes=50_000_000, fault=fault, control=control)


def test_sound_run_is_correct_and_its_control_is_not():
    r = tiny_run(tiny_cell())
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert r["sample"]["tokens"] > 0
    assert set(r["metrics"]) >= {"ttft_p50_ms", "itl_p50_ms", "itl_mean_ms",
                               "setup_s"}
    assert list(r)[-1] == "checks"
    c = tiny_run(tiny_cell(), control=True)
    assert not c["correct"], c["checks"]
    assert c["checks"]["widest_gap"]["value"] > TINY_LIMIT
    assert c["served_widest_gap"] <= TINY_LIMIT


@pytest.mark.parametrize("name", sorted(faults.FAULTS))
def test_fault_is_caught(name):
    r = tiny_run(tiny_cell(), faults.FAULTS[name])
    assert not r["correct"], r["checks"]
