"""Shared test scaffolding: build a cache with fakes, open sessions with
explicit tiers (the allocate_test.go:39-223 harness shape).

The builders live in volcano_tpu.bench.clusters, which the benchmark's
configuration check also reads; this module re-exports them plus the
session lifecycle helpers.
"""

from __future__ import annotations

from volcano_tpu.bench.clusters import make_cache, make_tiers  # noqa: F401
from volcano_tpu.scheduler.framework import open_session, close_session  # noqa: F401
import volcano_tpu.scheduler.actions  # noqa: F401  (register actions)
