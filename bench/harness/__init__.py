"""The benchmark's yardstick: everything between ``BENCHMARK.json`` and the
program under test.

* :mod:`.manifest` finds a cell's configuration, scheme, traffic mix, loop
  and metric readers by the names in ``BENCHMARK.json``;
* :mod:`.device` insists on the accelerator and reads its peaks table;
* :mod:`.fields` makes the seeded input fields on the device;
* :mod:`.generator` is what every closed loop in ``bench/loops/`` shares:
  units, windows, the seeded sample of answers, and the counts of compiles
  and of Python's collections;
* :mod:`.trace` and :mod:`.hlo` reduce a profiler trace to device busy
  time, kernel time and launch bytes;
* :mod:`.cell` runs one cell once and prints the result line;
* :mod:`.small` shrinks a copy of the benchmark for the tests on the CPU.

What is stepped or solved, its control and its plain reference live in
``bench/schemes/<scheme>.py``, one file per scheme.
"""
