"""Mean duration of the server's own ``serve:solve`` spans: one batch launch,
from dispatch to the host fetch of its totals."""


def read(run):
    spans = [s for name, s in run.spans if name == "serve:solve"]
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
