"""The chip benchmark of this repository: one data-driven harness, its
configurations, traffic mixes, per-layer metric readers and tests."""
