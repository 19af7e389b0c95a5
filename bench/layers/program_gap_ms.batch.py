"""Device: the device's idle time while the host waits for the engine's
scanned program (``sort.wait``; see bench/phases.py), in ms per engine
call the trace holds whole, mean over the chips: the gaps between the
program's own ops."""
import phases


def read(ctx):
    p = phases.of(ctx)
    return None if p is None else p.idle_ms((phases.WAIT_PHASE,))
