"""admm_roofline.model2: ``admm_roofline`` in the model-axis cell, where
each chip solves half a machine's CLIME columns (``machines_per_chip``
is 0.5: two machines on four chips).

The required work is half of one machine's two solves; the direction
solve (k = 1) runs on both chips of a machine and is counted once, a
share of 1/(d + 1) of the work.  Read by ``admm_roofline``'s own
function.
"""

import os

from bench import manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def read(summary):
    return manifest.load_reader(ROOT, "admm_roofline")(summary)
