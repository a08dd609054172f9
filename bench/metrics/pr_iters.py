"""Iterations the program's PageRank solve took to its tolerance."""
from bench.readers import iterations

read = iterations("pagerank")
