"""How late the load generator sent: the largest send time minus due time
in the window (a starved generator must not read as a fast server)."""


def read(run):
    lags = [(r.sent - r.due_s) * 1e3 for r in run.requests
            if r.due_s is not None and r.sent is not None]
    return max(lags) if lags else None
