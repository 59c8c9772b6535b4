"""Device: share of the traced window, in %, in which no operation ran,
averaged over the cell's chips (see bench.layers)."""

from bench.layers import device_idle_share as read  # noqa: F401
