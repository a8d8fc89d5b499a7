"""How far the faults that ``correct`` is there to catch move the
``kimi_linear_48b_a3b`` reference at the published widths and the timed
sizes, by both of the cell's limits: its loss (``TOLERANCE``) and its logits
at the witness's positions against the PROGRAM's (``LOGITS_TOLERANCE``, what
``drivers/train_scan_witnessed.py`` holds a run to; the larger of the
``edge`` and the ``spread`` group's third quartile, each printed).  The
reference with a fault put in (``reference.FAULTS``: no decay, beta = 1, no
``S'^T k`` subtraction, the gate before the norm, the shared key rotated,
the value's first lanes read off the shared key (what a value at the padded
width's wrong lanes would be), the shared key in head 0 alone, the shared
expert dropped, 7 of 8 experts, a route scale of 1 for 2.446, bfloat16
throughout), on the weights the program seeds and the cell's first batch.

    python3 benchmark/tools/kimi_linear_ref_sensitivity.py [seed] [out.json] [fault ...]

Faults named after the two are the only ones thrown; ``none`` throws none
and reads the sound program alone.  It is ``jamba_ref_sensitivity.py``'s
procedure (one definition of what is read and printed) on this
configuration and cell; the readings are the chip's alone."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.tools import jamba_ref_sensitivity as procedure  # noqa: E402

NAME, CELL = "kimi_linear_48b_a3b", "kimi_linear_48b_a3b.s16384_scan"


def main(*argv):
    procedure.NAME, procedure.CELL = NAME, CELL
    return procedure.main(*argv)


if __name__ == "__main__":
    main(*sys.argv[1:])
