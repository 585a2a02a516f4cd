"""Share of the traced window in which no operation ran on the device.

One reader for every cell: ``BENCHMARK.json`` splits the metric by the
end-to-end metric it moves (``device_idle_pct.backend`` in cells whose
pace the LLM backend sets, ``device_idle_pct.cache`` in those whose pace
the cache tier sets), and both names find this file."""
from harness.trace import idle_pct


def read(run):
    return idle_pct(run)
