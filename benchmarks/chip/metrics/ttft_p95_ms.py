"""95th percentile, over every request due inside the window, of its
first output's time minus its due time. A request with no output by the
end of the run's drain counts at that end: a lower bound of its wait."""
import numpy as np


def read(run):
    w = run.window
    waits = [(w.token_times[r][0] if w.token_times.get(r) else w.t_stop)
             - w.due[r] for r in w.due if w.in_window(r)]
    return float(np.percentile(waits, 95)) * 1e3 if waits else None
