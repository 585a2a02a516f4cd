"""Rows the engine served per batch: leader requests (coalesced waiters
ride on a leader) over ``AsyncScheduler.batches_served`` in the window."""


def read(run):
    if not run.batches_served:
        return None
    leaders = sum(r is not None and not r.coalesced
                  for r in run.records.resp)
    return leaders / run.batches_served
