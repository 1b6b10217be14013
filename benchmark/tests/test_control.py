"""The control comes out not correct: the reference computed in float8,
put in the program's place, reads past the limits that the sound run
(the program in bfloat16) keeps, here at the rehearsal's size and with
the rehearsal's own limits (set, like the cells', between the two
readings: see rehearsal.json and PERF.md). The benchmark's own runs do
not run the control."""

import pytest

from benchmark import run


@pytest.mark.parametrize("seed", [21, 2**31 + 22, 23])
def test_float8_control_fails_where_the_sound_run_passes(seed, rehearsal):
    line = run.run_cell("vit-h14.backlog", seed, 1.0, False,
                        rehearse=rehearsal, control=True)
    assert line["correct"] is True
    control = line["control"]
    assert control["correct"] is False
    assert control["precision"] == "fp8"
    assert control["delivery_faults"] == 0
    assert control["gap_max"] > rehearsal["correct"]["gap_max"]
    # the reading itself: the control lies well above the sound run
    assert control["gap_max"] > 2 * line["compared"]["gap_max"]["value"]
