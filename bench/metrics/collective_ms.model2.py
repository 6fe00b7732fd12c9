"""collective_ms.model2: ``collective_ms.fit4`` in the model-axis cell:
per fit on the busiest chip, each round's all-gather of the debias
correction over the model axis and its pmean over the data axis.  Read
by ``collective_ms.fit4``'s own function.
"""

import os

from bench import manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def read(summary):
    return manifest.load_reader(ROOT, "collective_ms.fit4")(summary)
