"""Round step (``ScaleEngine``'s round program): dense model FLOPs of the
window's rounds (forward and backward over every live local sample and
every evolve batch, from the configuration's FLOP function) over window
time x chips x the chip's bf16 peak, in %."""
LAYER = "round step (ScaleEngine round program)"
SOURCE = "host_clock"
MOVES = "client_rounds_per_s"


def read(ctx: dict):
    flops, window = ctx.get("model_flops"), ctx.get("window_s")
    if not flops or not window:
        return None
    return 100.0 * flops / (window * ctx["chips"] * ctx["peak"]["bf16_flops_per_s"])
