"""Service path (``fleet/mega/megasim.py``: completions, admissions,
the replicas' slot queues): the program's own host seconds in it
(``phase_timings["serve_s"]``, an accumulator inside the event loop) as
a share of job wall, over the window's untraced jobs."""


def read(rec):
    jobs = rec["jobs"]
    wall = sum(j["wall_s"] for j in jobs)
    if wall <= 0.0 or not all("serve_s" in j for j in jobs):
        return None
    return 100.0 * sum(j["serve_s"] for j in jobs) / wall
