"""setup_s (s, end to end, host clock): from the start of the process to the
first dispatch of the window: imports, data and weights from the seed, the
store's upload, and the first flush window of the driver loop, which compiles
(or reads from the persistent cache) every program the window uses."""


def read(run):
    return run["setup_s"]
