"""The on-chip benchmark's harness: everything the cells share.

``run.py`` finds a cell in ``BENCHMARK.json``; its configuration file names
the runner (``harness/<kind>.py``), its traffic file feeds the one generator
(``harness/traffic.py``), and each metric named in ``BENCHMARK.json`` is read
by ``metrics/<name>.py``.  Nothing here imports the program at module level:
the runners import it once the device has been checked.
"""
