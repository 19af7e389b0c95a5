"""Engine: the device's idle time while the host runs the engine call's
own host phases (``sort.prep``, ``sort.launch``, ``sort.collect``; see
bench/phases.py), in ms per engine call the trace holds whole, mean over
the chips."""
import phases


def read(ctx):
    p = phases.of(ctx)
    return None if p is None else p.idle_ms(phases.HOST_PHASES)
