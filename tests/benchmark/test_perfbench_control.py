"""The control: the plain reference computed in bfloat16 and put in the
program's place.  The comparison has to find it not correct, and each
number compared has to read above its limit on it."""

import pytest

from benchmark import check, control

SCALE = {"fleet": {"capture_events": 200_000, "n_vehicles": 2000}}


@pytest.mark.parametrize("workload", ["r9_replay", "pyramid_replay",
                                      "live_test", "multi_late_test",
                                      "hot_ramp_test"])
def test_bf16_control_is_not_correct(workload, bench_spec):
    numbers = control.readings(workload, 2**31 + 3, 600_000, spec=bench_spec,
                               scale=SCALE)
    lim = check.limits()
    assert not check.judge(numbers, lim)
    over = [k for k in check.NUMBERS if numbers[k] > lim[k]]
    assert set(over) == set(check.NUMBERS), numbers
