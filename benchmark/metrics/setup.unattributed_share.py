"""setup.unattributed_share (%): layer "set-up", moves setup_s.

The share of set-up, from the process's start to the window's start, that
none of the six ``setup.*`` seconds covers: in the harness, data and weights
from the seed, ``build`` outside its traces, the observability stack, the
telemetry session. The six seconds and this share of the total sum to the
total. Source: the program's spans; ``setup_reduce`` has the split."""

import setup_reduce


def read(run):
    got = setup_reduce.parts(run)
    return None if got is None or not got["total"] else 100.0 * got["unattributed"] / got["total"]
