"""One seed of each serving cell end to end on the CPU at tiny widths
(`cellbench/rehearse.py`): the warm-up compiles every step program the
pre-roll and the window then meet, no request fails, and the check
passes. Two dozen seeds were rehearsed this way before the chip was
asked (PERF.md); one seed a cell is kept as a test, at the chip's pace
(a CPU iteration is about six of the chip's, so the timetable is
stretched by six) and on the chip's own timetable, where this host is
six times too slow for it: the ramp is a closed loop and must not care.
On a timetable alone the second case met groups of 16 and 32 prompts and
sent no request inside the window. Slow: some minutes a case."""
import pytest

from cellbench import rehearse, run


@pytest.mark.slow
@pytest.mark.parametrize("slow", [6.0, 1.0],
                         ids=["chip-pace", "host-six-times-too-slow"])
@pytest.mark.parametrize("cell", [
    c["name"] for c in run.load_benchmark()["workloads"]])
def test_warm_up_covers_the_window(cell, slow):
    out = rehearse.rehearse(cell, 2**31 + 99, seconds=30.0, slow=slow)
    assert out["step_programs_preroll"] == 0
    assert out["step_programs_window"] == 0
    assert out["attempted"] > 20 and out["failed"] == 0
    assert out["tokens"] > 1000
    assert out["check"]["correct"] is True
