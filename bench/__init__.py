"""The chip benchmark of the fitting system; see bench/run.py."""
