"""Tests of the benchmark under ``benchmarks/suite`` (part of its paths)."""
