"""Mean device-idle gap between consecutive step programs in the traced
window: the host loop's time between steps."""
from bench.readings import host_gap_ms


def read(run):
    return host_gap_ms(run)
