"""``correct`` on ``heat3d.explicit`` at a small size: true for the program,
false for the lower-precision control and for each fault of the timed path."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.harness.small import run_small, small_root  # noqa: E402

CELL = "heat3d.explicit"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return small_root(tmp_path_factory.mktemp("bench"))


def _altered(env):
    out = {}
    for k, v in env.items():
        centre = tuple(n // 2 for n in v.shape)
        out[k] = v.at[centre].add(1.0)
    return out


def test_bench_stepping_program_is_correct(root):
    r = run_small(root, CELL)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0


def test_bench_stepping_control_is_not_correct(root):
    r = run_small(root, CELL, system="control")
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("fault", ["state_unchanged", "answer_altered"])
def test_bench_stepping_fault_is_not_correct(root, monkeypatch, fault):
    from repro.engine import executor

    orig = executor._trace_plan
    if fault == "state_unchanged":
        monkeypatch.setattr(executor, "_trace_plan", lambda plan, env: dict(env))
    else:
        monkeypatch.setattr(executor, "_trace_plan",
                            lambda plan, env: _altered(orig(plan, env)))
    r = run_small(root, CELL)
    assert not r["correct"], (fault, r["checks"])
