"""Backend rows generated per request answered, in percent: the paper's
cost metric (API calls saved). A row is generated for each answered
request that was neither a hit nor a coalesced waiter."""


def value(run):
    answered = [r for r in run.records.resp if r is not None]
    if not answered:
        return None
    generated = sum(not r.cached and not r.coalesced for r in answered)
    return 100.0 * generated / len(answered)
