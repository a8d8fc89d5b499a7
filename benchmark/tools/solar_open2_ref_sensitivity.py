"""How far the faults that ``correct`` is there to catch move the
``solar_open2_250b`` reference at the published widths and the timed sizes,
by both of the cell's limits: its loss (``TOLERANCE``) and its logits at the
witness's positions against the PROGRAM's (``LOGITS_TOLERANCE``, what
``drivers/train_scan_witnessed.py`` holds a run to; the larger of the
``edge`` and the ``spread`` group's third quartile, each printed).  The
reference with a fault put in (``reference.FAULTS``: ``beta`` without its
factor 2, a decay a head instead of a channel, no decay, no ``S'^T k``
subtraction, the gate before the norm, the grouped-query layer's gate left
out, query head i reading key/value head i mod 8, that layer rotated, the
shared expert dropped, 7 of 8 experts, bfloat16 throughout), on the weights
the program seeds and the cell's first batch.

    python3 benchmark/tools/solar_open2_ref_sensitivity.py [seed] [out.json] [fault ...]

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

NAME, CELL = "solar_open2_250b", "solar_open2_250b.s4096_scan"


def main(*argv):
    procedure.NAME, procedure.CELL = NAME, CELL
    return procedure.main(*argv)


if __name__ == "__main__":
    main(*sys.argv[1:])
