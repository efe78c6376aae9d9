"""Programs put in the program's place to show that ``correct`` fails.

The configurations state two guarantees (``guarantees`` in each
configuration file).  The control breaks the first, the one a faster
ingest path would be tempted to weaken: its ingest calls count every row
but leave the published block descriptions as they were, a later flush
in place of the exact one.  In a serving cell the layout then answers
from the descriptions the build left, a stale answer where it was exact.
"""

from __future__ import annotations

import numpy as np

from qdbench.system import Program


class StaleDescriptions(Program):
    """The control: ingest publishes counts but no descriptions."""

    def ingest(self, svc, batches):
        return np.asarray(svc.ingest(batches, tighten=False).block_sizes)
