"""The chip benchmark: one command runs one cell (a model configuration under
one traffic mix) once. See ``bench/run.py`` and ``BENCHMARK.json``."""
