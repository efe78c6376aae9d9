"""The qd-tree layout service's on-chip benchmark.

``bench/run.py`` is the entry point.  Everything that decides what a run
measures and whether its answers are right lives in this package, apart
from the program under test: the table and query generators, the plain
reference, the traffic generator, the reduction from a profiler trace to
metrics, and the table of device peaks.  The program is reached only
through :mod:`qdbench.system`.
"""
