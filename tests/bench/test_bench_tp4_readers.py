"""The ``.tp4`` readers on a hand-built run of four chips: the collective
share of busy time, the per-chip kernel rooflines and MFU (the whole
model's useful work over four chips' time), the runner's ticks and the
idle share; ``None`` wherever the run has nothing to read."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import readers  # noqa: E402
from bench.peaks import peaks  # noqa: E402
from bench.registry import Registry  # noqa: E402
from bench.serve import COUNTERS, Record, Sent, Step  # noqa: E402
from bench.work import Shapes  # noqa: E402
from bench.xplane import Summary  # noqa: E402

REG = Registry(ROOT)
SHAPES = Shapes(layers=2, d_model=256, heads=32, kv_heads=8, head_dim=8,
                d_ff=800, vocab=512)
PER_CHIP = ["block_gemm_roofline.tp4", "flash_attention_paged_roofline.tp4",
            "flash_decode_paged_roofline.tp4", "mfu.tp4"]
WHOLE = {"block_gemm_roofline.tp4": readers.roofline("block_gemm"),
         "flash_attention_paged_roofline.tp4":
             readers.roofline("flash_attention_paged"),
         "flash_decode_paged_roofline.tp4":
             readers.roofline("flash_decode_paged"),
         "mfu.tp4": readers.mfu}


def _run(trace=True, chips=4, replay_error=None):
    """A prompt of 20 rows in one chunk, then three tokens decoded, inside
    a 1 s trace of four devices."""
    a = Sent(0, list(range(20)), 4, 0.0)
    a.token_times = [0.2, 0.6, 0.6, 0.6]
    d = dict.fromkeys(COUNTERS, 0)
    steps = [Step(0.0, 0.2, d, chunk=(0, 0, 20)), Step(0.2, 0.6, d)]
    counters = dict.fromkeys(COUNTERS, (0, 0))
    counters.update(mixed_steps=(3, 5), chunks=(10, 14), prefill_s=(1.0, 1.3),
                    decode_s=(2.0, 3.0))
    rec = Record([a], steps, (0.0, 1.0), counters, 2.0, (0, 0),
                 trace=(0.0, 1.0), replay_error=replay_error)
    summary = Summary(window_s=1.0, busy_s=0.8, collective_s=0.2,
                      kernel_ops={"block_gemm": 0.3,
                                  "flash_attention_paged": 0.01,
                                  "flash_decode_paged": 0.02},
                      n_devices=4)
    return SimpleNamespace(record=rec, trace=summary if trace else None,
                           peaks=peaks("TPU v5 lite"), shapes=SHAPES,
                           chips=chips)


def test_collective_share_is_of_busy_time():
    assert REG.reader("collective_share.tp4")(_run()) == pytest.approx(25.0)


@pytest.mark.parametrize("name", PER_CHIP)
def test_per_chip_share_divides_the_whole_by_the_chips(name):
    whole = WHOLE[name](_run())
    assert whole > 0
    assert REG.reader(name)(_run()) == pytest.approx(whole / 4)
    assert REG.reader(name)(_run(chips=2)) == pytest.approx(whole / 2)


def test_ticks_and_idle_share():
    run = _run()
    assert REG.reader("mixed_tick_ms.tp4")(run) == pytest.approx(150.0)
    assert REG.reader("decode_tick_ms.tp4")(run) == pytest.approx(250.0)
    assert REG.reader("idle_share.tp4")(run) == pytest.approx(20.0)


@pytest.mark.parametrize("name", ["collective_share.tp4", "idle_share.tp4"]
                         + PER_CHIP)
def test_nothing_to_read_is_none(name):
    read = REG.reader(name)
    assert read(_run(trace=False)) is None
    if name in PER_CHIP:
        assert read(_run(replay_error="admitted while a prompt "
                                      "prefilled")) is None


def test_no_busy_time_reads_no_collective_share():
    run = _run()
    run.trace.busy_s = 0.0
    assert REG.reader("collective_share.tp4")(run) is None
