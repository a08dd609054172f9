from .analysis import (DEVICE_KIND_PROFILES, HW, HW_PROFILES,  # noqa: F401
                       model_flops, parse_collective_bytes, roofline_terms)
