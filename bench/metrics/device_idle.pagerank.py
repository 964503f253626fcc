"""Share of the traced slice in which no operation ran on the device."""

from bench.harness.readers import device_idle_pct as read  # noqa: F401
