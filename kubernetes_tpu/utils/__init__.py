"""Shared infra: metrics registry, span tracing, feature gates (component-base-lite)."""

from .metrics import Metrics, metrics  # noqa: F401
from .featuregate import FeatureGate, default_feature_gate  # noqa: F401
