"""Compile: the compiles that ended inside the timed window, read from
the program's compile counter (``repro.launch.compile_cache``; loads
from the persistent cache count too).  The batch cell's set-up makes
one engine call; the window's calls are the ones after it, and the
window is read from the start of the first to the end of the last.
None where the program has no counter, or started it later."""


def read(ctx):
    try:
        from repro.launch.compile_cache import compile_counter
    except ImportError:
        return None
    window = sorted(ctx.calls, key=lambda c: c.t0)[1:]
    if not window:
        return None
    start, end = window[0].t0, window[-1].t1
    counter = compile_counter()
    if counter.since > start:
        return None
    return counter.compiles_between(start, end)
