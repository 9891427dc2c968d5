"""flash_attention_paged_roofline.tp4: Kernels: roofline bound of the paged prefill attention's useful calls
over its device time, per chip.  The whole model's useful work is divided
over the chips that share each layer, against one chip's peaks and the
per-device mean kernel time; the activations every chip reads whole are
counted once, not per chip, so the share is a little low and never high.
Moves ttft_p90_s."""
from bench.readers import roofline

_whole = roofline("flash_attention_paged")


def read(run):
    v = _whole(run)
    return None if v is None else v / run.chips
