"""Programs lowered inside the measured window: lowering spans of the
program's compile log (``repro.obs.compile_log``) that start between the
window's open and its close. A steady window reads 0; more means a new
shape compiled on the request path.

One reader for every cell: ``BENCHMARK.json`` splits the metric by the
end-to-end metric it moves (``window_compiles.backend``,
``window_compiles.cache``), and both names find this file. A program
without a compile log reads nothing."""


def read(run):
    try:
        from repro.obs import compile_log
    except ImportError:
        return None
    spans = compile_log().spans(run.start, run.close)
    return sum(s.kind == "lowering" for s in spans)
