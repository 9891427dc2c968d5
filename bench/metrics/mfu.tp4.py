"""mfu.tp4: Model step: model FLOPs of the traced steps over the traced
interval times the bf16 peak of all the cell's chips (the whole model's
useful work divided over the chips that share each layer). Moves
ttft_p90_s."""
from bench.readers import mfu


def read(run):
    v = mfu(run)
    return None if v is None else v / run.chips
