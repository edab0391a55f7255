"""Walker proposals advanced, scored and passed through the selection
rules (the program's ``exchange.proposals`` counter), over the whole
window on the host clock."""


def read(rec):
    if rec["traffic"]["loop"] != "exchange":
        return None
    w = rec["window"]
    return w["work"] / w["elapsed"]
