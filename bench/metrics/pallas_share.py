"""Per cent of the traced solve's device-busy time in Pallas kernels,
against XLA's gathers and scatters (``pallas_share.<app>``)."""
from bench.readers import pallas_share as read  # noqa: F401
