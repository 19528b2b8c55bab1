"""Compiled bulk phases (``fleet/mega/jaxback.py``): their host wall
(``phase_timings["bulk_scan_s"]``, which holds dispatch, host-device
transfer and device time alike) as a share of job wall, over the
window's untraced jobs."""


def read(rec):
    wall = sum(j["wall_s"] for j in rec["jobs"])
    if wall <= 0.0:
        return None
    return 100.0 * sum(j["bulk_s"] for j in rec["jobs"]) / wall
