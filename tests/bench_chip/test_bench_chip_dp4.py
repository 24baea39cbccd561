"""A CPU rehearsal of the data-parallel stream cell on a 4x1 mesh of
host devices, as `test_bench_chip_rehearsal` rehearses the others. No
number from it means anything about speed."""
import time

from benchmarks.chip import run
from bench_chip_smoke import cnn_cell, no_persistent_cache

SEED = 2**31 + 977


def test_cnn_dp4_cell_rehearsal_on_four_devices(monkeypatch):
    no_persistent_cache(monkeypatch)
    monkeypatch.setenv("REPRO_QBACKEND", "pallas_interpret")
    cell = cnn_cell("resnet8-stream-dp4")
    cell.traffic["slots"] = 8
    assert cell.traffic["mesh"] == [4, 1]
    out = run.run_cell(cell, seed=SEED, seconds=2.0, trace=False,
                       require_tpu=False, t_start=time.perf_counter())
    assert out["correct"] is True
    assert out["check"]["mismatched_answers"]["value"] == 0
    assert set(out["metrics"]) == {"images_per_s", "setup_s"}
