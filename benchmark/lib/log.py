"""Lines on standard error that say how far a run has come."""
import sys
import time

T0 = time.perf_counter()       # set again by run.py to the process's start


def note(what):
    print("benchmark: %7.1f s  %s" % (time.perf_counter() - T0, what),
          file=sys.stderr, flush=True)
