"""The on-chip benchmark's own code: the yardstick the program is judged by.

Nothing here is imported by the program.  The harness (``harness``)
finds each cell's configuration, its model family, traffic, traffic
generator, loop and per-layer metric readers by name (``families/``,
``generators/``, ``loops/`` and ``metrics/`` hold one file each), drives
the program's timed path with what ``loops`` and ``traffic`` share,
reduces the profiler trace (``trace``), and counts the work each step
requires against the published peaks (``peaks``).  A family file holds
its counters and the plain float32 reference that decides ``correct``,
run on weights it makes itself; what the families share is ``family``.
"""
