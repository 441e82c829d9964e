"""On-chip benchmark of the served SpiDR path (``python3 bench/run.py``).

Everything that defines the measurement lives here: the traffic generator,
the plain reference and its chip-cost arithmetic, the network descriptions
they walk (``bench/networks/``), the operation and byte counts, the table
of peaks and the reduction from profiler traces to metrics.  The program
under test is imported only by ``bench/run.py``.
"""
