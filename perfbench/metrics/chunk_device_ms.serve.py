"""chunk_device_ms in cells with search traffic, where it moves their own
page rate, pages_per_s.serve."""
from perfbench.spec import reader

read = reader("chunk_device_ms")
