"""The on-chip benchmark's own code: the yardstick the program is judged by.

Nothing here is imported by the program.  The harness (``harness``)
finds each cell's configuration, traffic, traffic generator, loop and
per-layer metric readers by name (``generators/``, ``loops/`` and
``metrics/`` hold one file each), drives the program's timed path with
what ``loops`` and ``traffic`` share, reduces the profiler
trace (``trace``), counts the work each step requires (``counters``)
against the published peaks (``peaks``), and decides ``correct`` against
a plain float32 reference (``reference``) run on weights it makes itself
(``weights``).
"""
