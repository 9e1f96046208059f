"""Requests the router packed into each courier frame in the window
(``Router.stats()`` dispatches over frames). The in-process transport runs
a frame's calls one after another, and each ``generate`` call holds until
its sequence retires, so a frame of k requests reaches the engine one at a
time: above 1 this caps how many slots can fill."""


def read(run):
    frames = run.router.get("frames", 0)
    return run.router["dispatches"] / frames if frames else None
