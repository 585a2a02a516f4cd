"""Share of the embedder's feature-token lookups that its memo answered,
in percent: over leader requests whose trace lies inside the window
(``harness.spans``: from the start of the row's ``embed`` span to the end
of its last ``resolve`` span), the sum of ``meta["embed_memo_hits"]``
over the sum of ``meta["embed_features"]``, both annotated per row by
``CachedEngine.serve_batch``. The rest of the lookups hashed their token
with ``blake2s``.

One reader for every cell: ``BENCHMARK.json`` splits the metric by the
end-to-end metric it moves (``embed_memo_pct.backend``,
``embed_memo_pct.cache``), and both names find this file. A program that
annotates no lookups reads nothing."""
from harness.spans import inside


def read(run):
    hits = lookups = 0
    for tr in run.traces or ():
        n = tr.meta.get("embed_features")
        if n is None:
            continue
        starts = [s.t0 for s in tr.spans if s.name == "embed"]
        ends = [s.t1 for s in tr.spans if s.name == "resolve"]
        if starts and ends and inside(run, starts[0], ends[-1]):
            lookups += n
            hits += tr.meta["embed_memo_hits"]
    return 100.0 * hits / lookups if lookups else None
