"""Acquire/stage: host->device time per group, in ms (see bench.layers)."""

from bench.layers import transfer_ms_per_group as read  # noqa: F401
