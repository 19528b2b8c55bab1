"""Device idle share over one traced job's call into the program:
1 - (union of the intervals in which an operation ran on the device) /
(the call's wall, the time the rate counts)."""


def read(rec):
    tr = rec.get("trace")
    if not tr or tr["window_s"] <= 0.0 or tr["busy_s"] <= 0.0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
