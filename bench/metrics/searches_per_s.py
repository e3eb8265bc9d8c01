"""searches_per_s: searches (lanes) whose traces were fetched in the
window, over the window; the window closes when the matrix in flight at
its nominal end has finished, so all of its time holds counted work."""


def read(run):
    lanes = run.counts.get("lanes_done")
    if not lanes or not run.window_s:
        return None
    return lanes / run.window_s
