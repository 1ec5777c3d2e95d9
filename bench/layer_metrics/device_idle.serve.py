"""Share of the traced steady serving rounds in which no operation ran on
the device, in % (1 - busy union / traced window, averaged over chips)."""


def read(run):
    t = run["trace"]
    return None if not t else 100.0 * (1.0 - t["busy_s"] / t["window_s"])
