"""fit_mfu.model2: ``fit_mfu`` in the model-axis cell, over the whole
host: the required flops of the two machines held over four chips'
peak.  Work replicated over the model axis (the statistics, the eigh,
the direction solve) counts once: the second copy's time lowers this
share.  Read by ``fit_mfu``'s own function.
"""

import os

from bench import manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def read(summary):
    return manifest.load_reader(ROOT, "fit_mfu")(summary)
