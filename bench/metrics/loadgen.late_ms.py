"""loadgen.late_ms: 95th percentile of how late the generator submitted a
request after it was due (host clock).  A starved generator would read as
a fast server; this shows it."""
import numpy as np


def read(run):
    late = run.window.late_ms
    late = late[np.isfinite(late)]
    return float(np.percentile(late, 95)) if late.size else None
