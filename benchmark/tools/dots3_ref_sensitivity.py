"""How far the faults that ``correct`` is there to catch move the
``dots3_note_prev`` reference at the published widths and the timed sizes,
by both of the cell's limits: its loss (``TOLERANCE``) and its logits at the
witness's positions against the PROGRAM's (``LOGITS_TOLERANCE``, what
``drivers/train_scan_witnessed.py`` holds a run to; each group's third
quartile printed beside it: the positions before and from 513, where a
window first drops a key, before and from 2,048, where the indexer first
does, spread, and the last).  The reference with a fault put in
(``reference.FAULTS``: bfloat16 throughout, a window of 512 or 514, a
selection of 2,047, no rescale of the latents, the gate dropped, the
head-wise gate's weights read as an element-wise gate's, the sliding layers
at the full layers' theta, the wrong first head, no selection, un-rotated
indexer keys, the indexer's weights ``w`` dropped, 7 of 8 experts), on the
weights the program seeds and the cell's first batch.

    python3 benchmark/tools/dots3_ref_sensitivity.py [seed] [out.json] [fault ...]

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

NAME, CELL = "dots3_note_prev", "dots3_note_prev.s8192_scan"


def main(*argv):
    procedure.NAME, procedure.CELL = NAME, CELL
    return procedure.main(*argv)


if __name__ == "__main__":
    main(*sys.argv[1:])
