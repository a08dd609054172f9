"""Seconds of the program's DBG reorder, mapping and CSR rebuild, by its
own count (``ReorderResult.seconds``)."""
from bench.readers import phase

read = phase("reorder_program")
