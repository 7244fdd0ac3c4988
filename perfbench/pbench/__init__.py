"""The benchmark's own library: manifest loading, traffic generation, work
counts, trace reduction, the serving and training loops and the
correctness checks.

Nothing here is imported by the program under test; the program is imported
only by the loops (``serving.py``, ``training.py``), which take from it the
system under test and nothing else.
"""
