"""Set-up and the window's bookkeeping stay the same from seed to seed and
from the first second of a window to its last."""
from __future__ import annotations

import gc
import types

import numpy as np

from bench_tiny import SEED, tiny_spec
from harness import filler, window


def test_fill_program_does_not_depend_on_the_seed():
    """Every seed runs the one compiled fill, so only a checkout's first
    run compiles it."""
    spec = tiny_spec("paper-8k.faq-repeat")
    deployment = spec.deployment()
    corpus = deployment.corpus_of(spec.config)
    engine, _ = deployment.build(spec.config, corpus, SEED)
    prog = filler.fill_program(engine.cache, spec.config)
    texts = {prog.lower(engine.runtime, filler.seed_key(s)).as_text()
             for s in (1, 2 ** 33 + 5)}
    assert len(texts) == 1


def test_kept_reply_is_not_tracked_by_the_collector():
    """A reply is kept as atomic values only, so the window's records do
    not make a full collection slower as the window goes on."""
    resp = types.SimpleNamespace(answer="reset the router", cached=True,
                                 score=np.float32(0.93), latency_s=0.0123,
                                 coalesced=False, why={"decision": "hit"})
    kept = window._fields(resp)
    gc.collect()
    assert not gc.is_tracked(kept)
    rec = window.Records()
    rec.add(None, None)
    rec.resp[0] = kept
    rec.seal()
    assert rec.resp[0].cached and rec.resp[0].answer == "reset the router"
    assert abs(rec.resp[0].score - 0.93) < 1e-6
