"""Share of hits answered at their batch's peek, in percent: over leader
requests the engine served as hits (``meta["path"] == "hit"``) inside
the window (``harness.spans``, as ``hit_hold_ms`` counts them), those
whose trace is annotated ``release="early"``: released by
``CachedEngine.serve_batch`` before its batch's backend call, while the
rest of the hits are annotated ``release="batch"``.

One reader for every cell: ``BENCHMARK.json`` splits the metric by the
end-to-end metric it moves (``early_hit_pct.backend``,
``early_hit_pct.cache``), and both names find this file. A program that
annotates no release reads nothing."""
from harness.spans import inside


def read(run):
    early = n = 0
    for tr in run.traces or ():
        release = tr.meta.get("release")
        if tr.meta.get("path") != "hit" or release is None:
            continue
        ends = [s.t1 for s in tr.spans if s.name == "resolve"]
        if ends and inside(run, tr.meta["ready_t"], ends[-1]):
            n += 1
            early += release == "early"
    return 100.0 * early / n if n else None
