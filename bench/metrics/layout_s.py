"""Host seconds of the program's ELL layout, ``apps.to_arrays``, to its
arrays on the device."""
from bench.readers import phase

read = phase("layout")
