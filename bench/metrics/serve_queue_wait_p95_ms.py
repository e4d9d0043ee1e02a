"""95th percentile of the server's ``serve:queue_wait`` spans over the
window: from ``submit`` to the assembly of the batch that takes the
request."""

import statistics


def read(run):
    waits = [s for name, s in run.spans if name == "serve:queue_wait"]
    if len(waits) < 2:
        return None
    return 1e3 * statistics.quantiles(waits, n=20, method="inclusive")[-1]
