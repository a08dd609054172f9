"""Host seconds of the program's CSR build from the generated edge list
(``graph.csr.from_edges``)."""
from bench.readers import phase

read = phase("csr_build")
