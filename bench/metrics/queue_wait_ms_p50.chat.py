"""Median wait of the window's requests between arrival and admission
(``Request.t_admit - Request.t_arrival``, the program's own stamps on one
clock), in ms; None where the program does not stamp admissions."""
from bench.harness import stats


def read(run):
    clients = run.record.get("clients")
    if not clients:
        return None
    t0, t_end = run.record["window"]
    waits = [(c.req.t_admit - c.req.t_arrival) * 1e3 for c in clients
             if c.req is not None and t0 <= c.due <= t_end
             and getattr(c.req, "t_admit", None) is not None]
    return stats.percentile(waits, 50) if waits else None
