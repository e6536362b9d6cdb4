"""Idle share of the device in a steady window (_common.idle_pct)."""

from _common import idle_pct as read  # noqa: F401
