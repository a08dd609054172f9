"""Per cent of the traced window (one solve and the host work before it)
with no device operation (``device_idle.<app>``)."""
from bench.readers import device_idle as read  # noqa: F401
