"""The control comes out not correct.

The control is the plain reference put in the program's place and
computed one precision below the configuration's: bfloat16 for the
filter's float32.  At this tiny size on the CPU a run with the control in
the program's place reads ``correct`` false through the run's own
comparison, where the same run of the program reads true.  The same
readings at the cell's own size, on the chip, come from
``chipbench/calibrate.py``.
"""

from chipbench import run as run_lib


def test_control_fails_where_the_program_passes(tiny):
    def run(control):
        return run_lib.run_cell("rbpf.paper", 5, 2.0, False, root=tiny, require_chip=False,
                                control=control, log=lambda s: None)

    program, control = run(False), run(True)
    assert program["correct"], program["checks"]
    assert not control["correct"], control["checks"]
    over = [k for k, v in control["checks"].items()
            if isinstance(v["limit"], float) and v["value"] > v["limit"]]
    assert over, control["checks"]
