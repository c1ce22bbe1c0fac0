"""Reproducible synthetic clusters for the BASELINE.json configs: the tests
build from them, and benchmark/tests/test_configs.py checks the benchmark's
own generator against them."""

from volcano_tpu.bench.clusters import CONFIGS, build_config, make_tiers

__all__ = ["CONFIGS", "build_config", "make_tiers"]
