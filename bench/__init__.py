"""The benchmark of the fleet simulator on the accelerator (see run.py)."""
