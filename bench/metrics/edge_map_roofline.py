"""Share of the HBM roofline of the traced solve: int32-CSR compulsory
bytes over all device-busy time (``edge_map_roofline.<app>``)."""
from bench.readers import hbm_roofline as read  # noqa: F401
