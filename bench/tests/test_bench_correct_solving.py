"""``correct`` on ``btcs.cg`` at a small size: true for the program, false
for the lower-precision control and for each fault of the timed path."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import jax.numpy as jnp  # noqa: E402

from bench.harness.small import run_small, small_root  # noqa: E402

CELL = "btcs.cg"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return small_root(tmp_path_factory.mktemp("bench"))


def test_bench_solving_program_is_correct(root):
    r = run_small(root, CELL)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0


def test_bench_solving_control_is_not_correct(root):
    r = run_small(root, CELL, system="control")
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("fault", ["state_unchanged", "answer_altered",
                                   "tolerance_loosened", "stopped_early_said_converged",
                                   "not_converged"])
def test_bench_solving_fault_is_not_correct(root, monkeypatch, fault):
    from repro.solver import health, krylov

    orig = krylov.cg

    def unchanged(A, dot, b, x0, **kw):
        return x0, jnp.int32(1), jnp.float32(0.0), jnp.int32(health.CONVERGED)

    def altered(*a, **kw):
        x, i, res, outcome = orig(*a, **kw)
        return x.at[tuple(n // 2 for n in x.shape)].add(1.0), i, res, outcome

    def loosened(*a, **kw):  # the stopping test 100x looser than stated
        return orig(*a, **dict(kw, tol=1e-4))

    def early(*a, **kw):  # three iterations, reported as converged to 0
        x, i, _, _ = orig(*a, **dict(kw, maxiter=3))
        return x, i, jnp.float32(0.0), jnp.int32(health.CONVERGED)

    def maxiter(*a, **kw):  # the solver's own word says it did not converge
        x, i, res, _ = orig(*a, **kw)
        return x, i, res, jnp.int32(health.MAXITER)

    faults = {"state_unchanged": unchanged, "answer_altered": altered,
              "tolerance_loosened": loosened, "stopped_early_said_converged": early,
              "not_converged": maxiter}
    monkeypatch.setattr(krylov, "cg", faults[fault])
    r = run_small(root, CELL)
    assert not r["correct"], (fault, r["checks"])
