"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips, in percent: training cells."""


def read(rec):
    s = rec["trace"]
    if rec["traffic"]["loop"] != "train" or s is None or not s["devices"]:
        return None
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
