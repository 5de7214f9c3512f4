"""Hypothesis settings profiles shared by the property tests.

Two profiles are registered:

* ``ci`` — fixed-seed/deterministic (``derandomize=True``), fewer examples:
  the profile the CI ``pytest -m property`` step runs, so a red property
  job is reproducible rather than a roll of the dice;
* ``dev`` — more examples, randomized: what local runs get.

Select explicitly with ``HYPOTHESIS_PROFILE=ci|dev``; otherwise ``ci`` is
auto-picked when the ``CI`` env var is set. Tests that pass their own
``@settings(...)`` keep those values (profiles only fill the defaults).
"""
from __future__ import annotations

import os

from hypothesis import HealthCheck, given, settings
from hypothesis import strategies as st

settings.register_profile(
    "ci", max_examples=25, derandomize=True, deadline=None,
    suppress_health_check=[HealthCheck.too_slow])
settings.register_profile("dev", max_examples=75, deadline=None)
settings.load_profile(os.environ.get(
    "HYPOTHESIS_PROFILE", "ci" if os.environ.get("CI") else "dev"))

__all__ = ["given", "settings", "st"]
