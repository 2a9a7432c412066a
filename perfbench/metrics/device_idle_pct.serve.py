"""device_idle_pct in cells with search traffic, where the idle gaps lie
between query batches and so move the query tail."""
from perfbench.spec import reader

read = reader("device_idle_pct")
