"""The chip benchmark of the stencil solver: ``python3 bench/run.py``."""
