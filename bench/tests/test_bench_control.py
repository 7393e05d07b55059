"""The control: the float32 reference computed in float8 (one step below
the configuration's bfloat16), put in the program's place, reads above
each smoke configuration's limit while the program reads below it. The
same control, at the cells' own sizes on the chip, set the cells' limits
(``PERF.md``). CPU only."""
import sys
from pathlib import Path

import pytest

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parent)]

import smoke  # noqa: E402


@pytest.mark.parametrize("kind,seed", [("dense", 1), ("dense", 3),
                                       ("ssm", 1), ("ssm", 2)])
def test_control_fails_where_program_passes(kind, seed):
    out = smoke.run(kind, seed, control=True)
    chk = out["check"]
    assert out["correct"] is True, chk
    ctl = chk["control_max_logit_gap"]
    assert ctl["value"] > chk["max_logit_gap"]["limit"], chk
