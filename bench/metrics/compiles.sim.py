"""Programs JAX lowered inside the window (a jit cache miss each,
compiled afresh, since the window writes nothing to the persistent
cache), counted from JAX's monitoring events.  The simulator's billing
gather takes each day's arrivals unpadded, so every new day size
lowers one; padding its shapes would bring this to 0."""


def read(rec):
    return rec.get("compiles")
