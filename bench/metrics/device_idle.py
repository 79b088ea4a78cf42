"""Share of the traced window in which no operation ran on the device, %."""
from bench.readings import device_idle


def read(run):
    return device_idle(run)
