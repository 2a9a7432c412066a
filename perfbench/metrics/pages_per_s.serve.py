"""Pages fetched in the window over its wall time, in cells with search
traffic: a metric of its own there, since serving makes it spread far
wider than in crawl cells and so wants a wider bound."""
from perfbench.spec import reader

read = reader("pages_per_s")
