"""Drivers: each calls one public entry of the program.  A traffic file
names its driver; `run.py` imports `bench/drivers/<driver>.py`."""
