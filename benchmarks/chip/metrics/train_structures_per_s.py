"""Structures trained: K members x per-member batch x fused steps
completed (the program's ``train.fused_steps`` counter), over the whole
window on the host clock; the window ends on the trainer's host sync."""


def read(rec):
    if rec["traffic"]["loop"] != "train":
        return None
    w = rec["window"]
    return w["work"] / w["elapsed"]
