"""Device: 1 - (union of the intervals in which an op runs on the chip) /
(traced window), averaged over the chips, in %, from the profiler trace of
the training window."""
LAYER = "device"
SOURCE = "device_trace"
MOVES = "client_rounds_per_s"


def read(ctx: dict):
    red = ctx.get("trace")
    if not red or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
