"""The chip benchmark: one command runs one cell (a model
configuration under a traffic mix) once; see ``bench/run.py``."""
