"""The Pallas support-count kernel's share of its HBM roofline in the
engine's programs of the traced window, in % (see support_count_bytes.py)."""

from bench.metrics.common import support_count_roofline as read  # noqa: F401
