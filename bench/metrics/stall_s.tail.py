"""Seconds of the window in which the service stalled: the program's
``stall`` spans (work in flight, no working tick finished for 0.5 s or
more), clipped to the window.  None where the service keeps no stall
watchdog (no ``service.stalls`` counter)."""


def read(run):
    if "service.stalls" not in run.counters:
        return None
    return sum(max(0.0, min(s.t1, run.t_close) - max(s.t0, run.t_open))
               for s in run.spans if s.name == "stall" and s.t1 >= 0)
