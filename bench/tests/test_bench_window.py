"""The closed backlog's window closes on a whole number of wave periods:
at the first wave boundary at or after its length at which the waves
served in it number a multiple of the mix's ``close_waves``."""
import io
import re

import benchtest
import pytest


def _waves_in_window(tmp_path, close_waves):
    import run as bench_run
    mix = dict(benchtest.SMALL_MIX)
    if close_waves is not None:
        mix["close_waves"] = close_waves
    reg = benchtest.small_registry(tmp_path, mix=mix)
    args = bench_run.parse_args(["--workload", "small", "--seed", "7",
                                 "--seconds", "0.3", "--trace", "0"])
    err = io.StringIO()
    line = bench_run.execute(args, registry=reg, require_chip=False,
                             use_cache=False, err=err)
    assert line["correct"], line["checks"]
    return int(re.search(r"^run waves (\d+)$", err.getvalue(),
                         re.M).group(1))


@pytest.mark.parametrize("close_waves", [None, 3])
def test_the_window_holds_whole_periods_of_waves(tmp_path, close_waves):
    waves = _waves_in_window(tmp_path, close_waves)
    assert waves > 0
    assert waves % (close_waves or 1) == 0


def test_a_mix_closes_on_single_waves_by_default():
    from benchlib.traffic import Mix
    assert Mix.from_dict("m", benchtest.SMALL_MIX).close_waves == 1
    assert Mix.from_dict("m", dict(benchtest.SMALL_MIX,
                                   close_waves=2)).close_waves == 2
