"""One caller steps device-resident state chunk after chunk: each chunk's
output is the next chunk's input.

Traffic keys: ``chunk_steps`` (steps a chunk) and ``sample`` (chunks whose
input and output are kept, drawn by the seed, for the check).  The scheme
gives ``inputs``, ``stepper`` and ``compare_steps``.
"""
import time

import jax
import jax.numpy as jnp

from bench.harness import generator as g


def _copy(x):
    return jnp.array(x, copy=True)


class Loop(g.Loop):
    NEEDS = ("inputs", "stepper", "compare_steps")

    def setup(self):
        self.stepper = self.scheme.stepper(self.config, self.traffic, self.devices,
                                           self.control)
        self.info.update(self.stepper.info)
        (x,) = self.scheme.inputs(self.config, self.seed, 1)
        for _ in range(2):  # compile (or load) the runner and the sample copy
            kept = _copy(x)
            x = self.stepper.run(x)
            jax.block_until_ready((kept, x))
        del kept
        self.state = x

    def window(self, seconds: float) -> g.Window:
        keep = g.Reservoir(int(self.traffic["sample"]), self.rng)
        work = float(self.cells * self.stepper.steps)
        units, x, c0 = [], self.state, g.Compiles.n
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            while True:
                i = len(units)
                start = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.chunk"):
                    inp = _copy(x) if keep.wants(i) else None
                    with jax.profiler.TraceAnnotation("bench.dispatch"):
                        x = self.stepper.run(x)
                    with jax.profiler.TraceAnnotation("bench.wait"):
                        jax.block_until_ready(x)
                    if inp is not None:
                        keep.put((inp, _copy(x)))
                end = time.perf_counter()
                units.append(g.Unit(start, end, True, work))
                if end - t0 >= seconds:
                    break
        self.state = None
        self.samples = keep.sample()
        return g.Window(t0, end, units, g.Compiles.n - c0)

    def check(self, window: g.Window) -> dict:
        numbers = {}
        for inp, out in self.samples:
            g.worst(numbers, self.scheme.compare_steps(self.config, inp, out,
                                                       self.stepper.steps))
        self.samples = []
        return numbers
