"""Tests of the benchmark (CPU): python -m pytest benchmark/tests."""
