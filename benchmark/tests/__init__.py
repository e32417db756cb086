"""Rehearsals of the benchmark; see conftest.py."""
