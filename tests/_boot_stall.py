"""A node preload that holds the interpreter lock for a while during boot,
as the start of a TPU client does: no other thread of the node (its
heartbeat included) runs until it is done."""

import sys
import time

HOLD_S = 1.5

time.sleep(0.3)  # let the node register and start its heartbeat first
_interval = sys.getswitchinterval()
sys.setswitchinterval(60.0)  # no thread switch while this loop spins
try:
    _end = time.perf_counter() + HOLD_S
    while time.perf_counter() < _end:
        pass
finally:
    sys.setswitchinterval(_interval)
