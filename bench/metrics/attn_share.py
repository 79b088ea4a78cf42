"""Share of the step programs' device time that the paged attention
kernel takes in the traced window, in %."""
from bench.readings import traced_steps


def read(run):
    steps = traced_steps(run)
    device = sum(t["device_s"] for _, t in steps)
    if not device:
        return None
    return 100.0 * sum(t["kernel_s"].get("paged_attn", 0.0)
                       for _, t in steps) / device
