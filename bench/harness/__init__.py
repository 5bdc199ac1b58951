"""Shared harness: cells, traffic, timing, traces, checks."""
