"""95th percentile (nearest rank), over every request due in the window, of
the time from when it was due to its result. A request that failed or never
came counts as above any limit."""
from bench import traffic


def read(run):
    lat = run.window.latencies_ms()
    return traffic.nearest_rank(lat, 0.95) if lat else None
