"""Router dispatch cost: ``Router.stats()`` dispatch time summed over the
window's dispatches, over their number (microseconds per dispatch)."""


def read(run):
    n = run.router.get("dispatches", 0)
    return run.router["dispatch_us_sum"] / n if n else None
