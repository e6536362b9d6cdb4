"""Idle share of the device over a traced retrain cycle (_common.idle_pct)."""

from _common import idle_pct as read  # noqa: F401
