"""Share of the exchange loop's time spent outside the fused fleet
dispatch: 1 - (the program's ``exchange.predict`` timer, which spans
``fleet.step()`` and its device sync) / (the benchmark's span around
``Exchange.step()``), in percent, over the window."""


def read(rec):
    if rec["traffic"]["loop"] != "exchange":
        return None
    step = rec["spans"].get("exchange.step", 0.0)
    if step <= 0:
        return None
    return 100.0 * (1.0 - rec["window"]["predict_s"] / step)
