"""One caller runs independent solves back to back; each solve's state and
right-hand side cycle through ``fields`` seeded fields, so the work per
solve does not drift with speed.

Traffic keys: ``fields`` and ``sample`` (solves whose answers are kept,
drawn by the seed, for the check).  The scheme gives ``inputs``,
``solver`` and ``compare_solve``.  Besides the scheme's comparison the
check reads two of the configuration's guarantees over every solve in the
window: ``not_converged``, the solves the solver did not call converged,
and ``reported_residual``, the largest residual norm it stopped at.
"""
import time

import jax

from bench.harness import generator as g


class Loop(g.Loop):
    NEEDS = ("inputs", "solver", "compare_solve")

    def setup(self):
        self.solver = self.scheme.solver(self.config, self.traffic, self.devices,
                                         self.control)
        self.info.update(self.solver.info)
        n = int(self.traffic["fields"])
        self.fields = self.scheme.inputs(self.config, self.seed, n)
        self.order = self.rng.permutation(n)
        for _ in range(2):
            x, raw = self.solver.solve(self.fields[0])
            jax.block_until_ready((x, raw))
        del x, raw

    def window(self, seconds: float) -> g.Window:
        keep = g.Reservoir(int(self.traffic["sample"]), self.rng)
        units, c0 = [], g.Compiles.n
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            while True:
                i = len(units)
                idx = int(self.order[i % len(self.order)])
                start = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.solve"):
                    with jax.profiler.TraceAnnotation("bench.dispatch"):
                        x, raw = self.solver.solve(self.fields[idx])
                    with jax.profiler.TraceAnnotation("bench.wait"):
                        raw = jax.device_get(raw)
                        jax.block_until_ready(x)
                iters, ok, res = self.solver.read(raw)
                end = time.perf_counter()
                if keep.wants(i):
                    keep.put((idx, x))
                del x
                units.append(g.Unit(start, end, ok, 0.0,
                                    {"iterations": iters, "residual": res}))
                if end - t0 >= seconds:
                    break
        self.samples = keep.sample()
        return g.Window(t0, end, units, g.Compiles.n - c0)

    def check(self, window: g.Window) -> dict:
        numbers = {"not_converged": float(window.failed),
                   "reported_residual": max(u.extra["residual"] for u in window.units)}
        for idx, x in self.samples:
            g.worst(numbers, self.scheme.compare_solve(self.config, self.fields[idx], x))
        self.samples, self.fields = [], None
        return numbers
