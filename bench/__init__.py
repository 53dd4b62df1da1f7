"""Chip benchmark of the served path: configurations, traffic mixes, the
open-loop harness, the plain reference and the trace reduction."""
