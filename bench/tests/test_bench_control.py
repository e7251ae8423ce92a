"""The control — the reference one step below the stated precision, in
the program's place — comes out as not correct by the harness's own
judgement, while the program, on the same traffic, comes out correct."""
import benchtest


def test_control_fails_where_the_program_passes(tmp_path):
    line = benchtest.run_small(tmp_path, seconds=0.5, control=True)
    assert line["program"]["correct"], line["program"]["checks"]
    assert not line["correct"], line["checks"]
    assert list(line)[-1] == "checks"
