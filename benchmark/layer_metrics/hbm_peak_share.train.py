"""``peak_bytes_in_use`` over ``bytes_limit`` of the fullest chip, read
after the window."""


def read(r):
    mem = r["memory"]
    if not mem.get("peak") or not mem.get("limit"):
        return None
    return 100.0 * mem["peak"] / mem["limit"]
