"""Small copies of the benchmark for the tests on the CPU.

:func:`small_root` copies ``BENCHMARK.json`` and ``bench/`` into a
directory and shrinks every configuration's grid, so a whole run of a cell
(set-up, window, check) takes seconds in Pallas interpret mode.
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path

from .manifest import ROOT

#: the grid every configuration takes in the tests
SMALL_GRID = [16, 16, 8]


def small_root(dest: Path) -> Path:
    dest = Path(dest)
    shutil.copytree(ROOT / "bench", dest / "bench", dirs_exist_ok=True)
    shutil.copy(ROOT / "BENCHMARK.json", dest / "BENCHMARK.json")
    for path in (dest / "bench" / "configs").glob("*.json"):
        cfg = json.loads(path.read_text())
        cfg["grid"] = SMALL_GRID
        path.write_text(json.dumps(cfg))
    return dest


def run_small(root: Path, name: str, system: str = "program", seed: int = 2**31 + 11,
              seconds: float = 0.2) -> dict:
    """One untraced run of cell ``name`` on whatever devices JAX has."""
    from . import cell

    return cell.run(name, seed, seconds, False, t_process=0.0, root=root,
                    system=system, require_tpu=False, cache=False,
                    log=lambda s: None)
