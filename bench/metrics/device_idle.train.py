"""device_idle.train (%): 1 - busy / window, where busy is the union of the
intervals in which an op ran on the device during the traced window,
averaged over the devices used."""


def read(facts: dict, trace):
    if trace is None or trace.window_s <= 0:
        return None
    return 100.0 * trace.idle_share
