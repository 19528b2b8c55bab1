"""Host event loop: microseconds of job wall outside the compiled bulk
phases, per simulated request (``fleet/mega/megasim.py``).

Job wall from the host clock, bulk time from the program's own
``phase_timings["bulk_scan_s"]``, over the window's untraced jobs."""


def read(rec):
    jobs = rec["jobs"]
    reqs = sum(j["requests"] for j in jobs)
    if not reqs:
        return None
    wall = sum(j["wall_s"] for j in jobs)
    bulk = sum(j["bulk_s"] for j in jobs)
    return (wall - bulk) / reqs * 1e6
