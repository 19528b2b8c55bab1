"""Host event loop: the program's own event-loop seconds
(``phase_timings["event_loop_s"]``, the ``mega.event_loop`` span, which
holds the service path) per simulated request, in microseconds, over
the window's untraced jobs."""


def read(rec):
    jobs = rec["jobs"]
    reqs = sum(j["requests"] for j in jobs)
    if not reqs or not all("event_loop_s" in j for j in jobs):
        return None
    return sum(j["event_loop_s"] for j in jobs) / reqs * 1e6
