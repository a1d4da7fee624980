"""Images over bucket rows of the steps in the window: the serving
runtime's ``stats()`` counters, taken as differences over the window."""


def read(run):
    rows = run.stats1["total_rows"] - run.stats0["total_rows"]
    if rows <= 0:
        return None
    return 100.0 * (run.stats1["images"] - run.stats0["images"]) / rows
