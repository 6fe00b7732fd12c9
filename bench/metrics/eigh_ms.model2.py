"""eigh_ms.model2: ``eigh_ms`` in the model-axis cell.  Both chips of a
machine factor the same Sigma-hat (the statistics, the factor and the
direction solve are replicated over the model axis), so this is work
the column split does not divide.  Read by ``eigh_ms``'s own function.
"""

import os

from bench import manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def read(summary):
    return manifest.load_reader(ROOT, "eigh_ms")(summary)
