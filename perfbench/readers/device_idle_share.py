"""Device: 1 - (union of executed op intervals) / (traced window), in
percent, averaged over the chips."""


def read(run):
    if not run.trace:
        return None
    return 100.0 * run.trace["idle_share"]
