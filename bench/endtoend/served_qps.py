"""Requests answered inside the window, per second of the window."""


def value(run):
    rec = run.records
    n = sum(1 for i in range(len(rec))
            if rec.resp[i] is not None and rec.t_obs[i] <= run.close)
    return n / run.seconds
