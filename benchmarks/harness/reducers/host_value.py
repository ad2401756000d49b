"""A number the traffic loop measured on the host's clock or counted
(``result["host"][key]``): lateness, waits, occupancy, step time."""


def read(ctx, key: str, scale: float = 1.0):
    v = ctx["host"].get(key)
    return None if v is None else scale * v
